"""Share of looked-up prompt tokens that the prefix cache served, over the
window, in %: the difference of ``prefix_hit_tokens`` over the difference
of ``prefix_lookup_tokens`` in ``ServingEngine.stats()``. Layer: prefix
cache."""


def read(run):
    looked = (run.stats1["prefix_lookup_tokens"]
              - run.stats0["prefix_lookup_tokens"])
    if looked <= 0:
        return None
    hit = run.stats1["prefix_hit_tokens"] - run.stats0["prefix_hit_tokens"]
    return 100.0 * hit / looked
