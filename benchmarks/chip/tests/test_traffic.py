"""The traffic generator: the same seed gives the same requests, and
every seed serves the same sizes on the same schedule, with other text."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import smoke  # noqa: F401  (puts the benchmark on the path)
from traffic import BLOCK, Traffic, doc_blocks, tokens

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2 ** 33 + 17          # seeds may exceed 32 bits


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def sizes(specs):
    return Counter((s.doc, s.prompt_tokens, s.max_new_tokens)
                   if s.doc is not None else (s.prompt_tokens,
                                              s.max_new_tokens)
                   for s in specs)


@pytest.mark.parametrize("name", ["rag-open", "unique-batch"])
def test_same_seed_same_requests(name):
    t = load(name)
    if t["loop"] == "open":
        a, b = (Traffic(t, BIG).open_loop(30.0) for _ in range(2))
    else:
        ta, tb = Traffic(t, BIG), Traffic(t, BIG)
        a = [ta.next_closed() for _ in range(40)]
        b = [tb.next_closed() for _ in range(40)]
    assert a == b


def test_open_loop_same_schedule_other_text():
    t = load("rag-open")
    a, b = Traffic(t, 1).open_loop(30.0), Traffic(t, BIG).open_loop(30.0)
    assert len(a) == len(b) == round(t["rate_rps"] * 30)
    assert [(s.doc, s.prompt_tokens, s.max_new_tokens, s.due_s)
            for s in a] == [(s.doc, s.prompt_tokens, s.max_new_tokens,
                             s.due_s) for s in b]
    assert [s.prompt for s in a] != [s.prompt for s in b]
    due = [s.due_s for s in a]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0


def test_open_loop_gaps_are_one_set():
    t = load("rag-open")
    d = [s.due_s for s in Traffic(t, 3).open_loop(60.0)]
    gaps = np.diff(d + [60.0])
    n = len(gaps)
    want = np.asarray([-np.log(1 - (i + 0.5) / n) for i in range(n)])
    # the stratified exponential quantiles, scaled to fill the window
    np.testing.assert_allclose(np.sort(gaps), np.sort(want) * 60.0
                               / want.sum(), rtol=1e-9)


def test_closed_rounds_same_sizes():
    t = load("unique-batch")
    rnd = t["clients"] * t.get("rounds_per_client", 2)
    ta, tb = Traffic(t, 5), Traffic(t, BIG)
    a = [ta.next_closed() for _ in range(2 * rnd)]
    b = [tb.next_closed() for _ in range(2 * rnd)]
    assert sizes(a) == sizes(b)
    assert sizes(a[:rnd]) == sizes(a[rnd:])


def test_documents_shared_or_unique():
    rag = Traffic(load("rag-open"), 9).open_loop(30.0)
    docs = {}
    for s in rag:
        n = doc_blocks(load("rag-open"))[s.doc] * BLOCK - 1
        docs.setdefault(s.doc, set()).add(s.prompt[:n])
    assert all(len(v) == 1 for v in docs.values())
    t = load("unique-batch")
    tr = Traffic(t, 9)
    firsts = [tr.next_closed().prompt[:BLOCK] for _ in range(64)]
    assert len(set(firsts)) == 64


def test_prompt_lengths_and_tokens():
    t = load("rag-open")
    for s in Traffic(t, 11).open_loop(30.0):
        toks = tokens(s.prompt)
        assert len(toks) == s.prompt_tokens
        q = s.prompt_tokens - doc_blocks(t)[s.doc] * BLOCK
        assert t["question_tokens"]["min"] <= q <= t["question_tokens"]["max"]
        out = t["output_tokens"]
        assert out["min"] <= s.max_new_tokens <= out["max"]
