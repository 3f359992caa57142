"""Device time per record of the sorts the per-leaf DGC quantile
(`jnp.quantile` in `core/accumulator.leaf_threshold`) lowers to: the
sorts of (cohort, leaf size) rows.  Alg. 2's percentile sorts one row
of scores and is not counted."""

OP = r"^%sort[.\d]* = \(f32\[\d+,\d+\]"


def read(run):
    from bench import trace
    seconds = sum(s for _, s in trace.matches(run.trace, OP))
    if seconds <= 0:
        return None
    return 1e3 * seconds / run.records
