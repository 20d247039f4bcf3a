"""Host time of a scheduling round outside the device sync (ms): the
serving loop's wall seconds in rounds that had work, less the seconds
it blocked reading tokens back, over those rounds (EngineStats counters
over the window).  The part of each step the device can sit idle for."""


def read(run):
    s = run.stats
    if not s.get("rounds"):
        return None
    return 1e3 * (s["round_s"] - s["step_sync_s"]) / s["rounds"]
