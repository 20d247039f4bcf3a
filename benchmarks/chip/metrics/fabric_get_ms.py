"""Wall time of one Get KVC on the constellation (ms): EngineStats
``fabric_get_s`` over ``fabric_gets`` in the window; silent when there
was no Get."""


def read(run):
    s = run.stats
    if not s.get("fabric_gets"):
        return None
    return 1e3 * s["fabric_get_s"] / s["fabric_gets"]
