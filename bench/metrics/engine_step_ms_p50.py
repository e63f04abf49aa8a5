"""Median host time of one ``ServingEngine.step()`` call inside the window,
in ms: the benchmark's own clock around the call, which ends in the host
sync of the sampled ids. Layer: engine and scheduler."""
import statistics


def read(run):
    ws, we = run.window
    steps = [s for s, end in zip(run.clients.step_s, run.clients.step_at)
             if ws <= end - s and end < we]
    return 1e3 * statistics.median(steps) if steps else None
