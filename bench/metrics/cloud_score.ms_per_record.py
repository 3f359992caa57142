"""Device self time of cloud scoring per record: the ops whose HLO
`op_name` carries the ``fleet.cloud_score`` scope (every upload's model
rebuilt and scored on the cloud set, and Alg. 2's percentile and mask),
in ms over the ``round`` spans in the traced window."""


def read(run):
    from bench import stages
    return stages.scope_ms_per_record(run, "fleet.cloud_score")
