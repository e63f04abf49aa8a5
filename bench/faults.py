"""Faults planted under the timed path, each of which the comparison with
the reference has to catch: a decode step that returns its state
unchanged (the KV pool is never written), half of the batch left out (its
logits replaced by the other half's), and a token altered where it is
produced. Each is a hook ``fault(eng)`` that breaks a ``ServingEngine``
in place; ``bench/tests/test_faults.py`` runs them at smoke size, and
``bench/calibrate.py --faults`` at a cell's own size.
"""


def state_unchanged(eng) -> None:
    decode = eng.decode

    def broken(params, tokens, positions, caches, bt=None):
        logits, _ = decode(params, tokens, positions, caches, bt)
        return logits, caches                # the KV pool is never written
    eng.decode = broken


def half_batch(eng) -> None:
    decode = eng.decode

    def broken(params, tokens, positions, caches, bt=None):
        logits, caches = decode(params, tokens, positions, caches, bt)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half]), caches
    eng.decode = broken


def token_altered(eng) -> None:
    step = eng.step
    seen = {}

    def broken(*a, **k):
        out = step(*a, **k)
        for rid in out:                      # each request's third token
            seen[rid] = seen.get(rid, 0) + 1
            if seen[rid] == 3:
                out[rid] = (out[rid] + 1) % eng.cfg.vocab
        return out
    eng.step = broken


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}
