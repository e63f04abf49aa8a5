"""Share of the rows the model computed over the window that held a real
token, in %: the difference of ``prefill_tokens + decode_tokens`` over the
difference of ``prefill_rows + decode_rows`` (``B * Sb`` per prefill call,
``B`` per decode call, padding included) in ``ServingEngine.stats()``.
Layer: model step."""

TOKENS = ("prefill_tokens", "decode_tokens")
ROWS = ("prefill_rows", "decode_rows")


def read(run):
    s0, s1 = run.stats0, run.stats1
    if not all(k in s0 and k in s1 for k in TOKENS + ROWS):
        return None
    rows = sum(s1[k] - s0[k] for k in ROWS)
    if rows <= 0:
        return None
    return 100.0 * sum(s1[k] - s0[k] for k in TOKENS) / rows
