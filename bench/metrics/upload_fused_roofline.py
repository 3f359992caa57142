"""The fused upload kernel's share of its HBM roofline: 16 C P bytes per
call (`bench/work.py`) at the chip's HBM peak, over the kernel's device
time in the trace.  The kernel's op is the one its `pallas_call` names,
``%upload_fused.N``; C, the call's cohort, is read from its first output,
the (C, rows, 1024) upload block, and P is the model's parameter count
(`run.n_params`), which those rows have to hold: the padding up to a
whole block moves bytes the algorithm does not need.  Calls whose outputs
the compiler placed in on-chip memory (layout `S(1)`, as it does at a
cohort of 10) move no HBM bytes and are not counted."""

OP = r"^%upload_fused(?:\.\d+)? = \(f32\[(\d+),(\d+),1024\](\{[^}]*\})"


def read(run):
    from bench import trace, work
    calls = [(m, s) for m, s in trace.matches(run.trace, OP)
             if "S(1)" not in m.group(3)]
    seconds = sum(s for _, s in calls)
    if not calls or seconds <= 0:
        return None
    for m, _ in calls:
        if int(m.group(2)) * 1024 < run.n_params:
            raise ValueError(f"{m.string[:120]!r} holds fewer than the "
                             f"model's P = {run.n_params} floats a node")
    nbytes = sum(work.upload_fused_bytes(int(m.group(1)), run.n_params)
                 for m, _ in calls)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds
