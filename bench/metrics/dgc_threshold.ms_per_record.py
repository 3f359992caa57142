"""Device time per record of the sorts the per-leaf DGC quantile
(`jnp.quantile` in `core/accumulator.leaf_threshold`) lowers to: the
`sort` ops whose HLO `op_name` carries the ``fleet.upload`` scope, one of
(cohort, leaf size) rows per leaf.  Alg. 2's percentile sorts under
``fleet.cloud_score`` and is not counted."""


def read(run):
    from bench import stages
    seconds = stages.op_seconds(run, "sort", "fleet.upload")
    if seconds <= 0:
        return None
    return 1e3 * seconds / run.records
