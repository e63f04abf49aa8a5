"""The engine's own host work per engine tick over the window, in ms: the
difference of ``engine_s - engine_wait_s`` (host seconds inside
``ServingEngine.submit()``/``step()``/``cancel()``, less those blocked on
the device inside ``engine.wait``: a result, or a call still writing the
caches before a dispatch that needs a fresh copy of them) over the
difference of ``tick`` (one per ``step()`` or ``submit()`` call), from
``ServingEngine.stats()``. Layer: engine and scheduler."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    keys = ("engine_s", "engine_wait_s", "tick")
    if not all(k in s0 and k in s1 for k in keys):
        return None
    ticks = s1["tick"] - s0["tick"]
    if ticks <= 0:
        return None
    own = ((s1["engine_s"] - s0["engine_s"])
           - (s1["engine_wait_s"] - s0["engine_wait_s"]))
    return 1e3 * own / ticks
