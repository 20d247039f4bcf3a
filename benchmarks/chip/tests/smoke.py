"""A configuration and traffic at smoke widths for the CPU tests: the
real harness and program, shapes small enough for the CPU backend."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

CONFIG = {
    "name": "smoke", "source": "test", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "deployment": {"block": 128, "max_batch": 4, "max_seq_len": 1024,
                   "chunk_tokens": 256, "chunk_bytes": 65536,
                   "per_sat_capacity_bytes": None,
                   "cpu_pages": 48, "check_rows": 16},
}


def traffic(name: str) -> dict:
    t = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    t["documents"] = {"count": 2, "blocks": [1, 3], "zipf": 1.0}
    t["question_tokens"] = {"median": 40, "sigma": 0.5, "min": 8,
                            "max": 130}
    t["output_tokens"] = {"median": 6, "sigma": 0.4, "min": 3, "max": 12}
    t["drain_s"] = 120
    t["check"] = dict(t["check"], requests=2, min_tokens=4)
    if t["loop"] == "open":
        t["rate_rps"] = 2.0
    else:
        t["clients"] = 2
    return t


def cell(name: str, trace: bool = False, config=None) -> harness.Cell:
    """The smoke cell of traffic ``name``: the metrics of the benchmark's
    cell with that traffic, or, for a traffic file that no cell uses yet,
    every metric of the kind the run reports."""
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    w = next((w for w in bench["workloads"] if w["traffic"] == name), None)
    if w is not None:
        c = harness.cell(w["name"], trace, bench)
    else:
        c = harness.Cell(name=name, config={}, traffic={}, chips=1,
                         metrics=bench["per_layer" if trace
                                       else "end_to_end"])
    c.config = copy.deepcopy(config or CONFIG)
    c.traffic = traffic(name)
    return c
