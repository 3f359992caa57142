"""The fused upload kernel's share of its HBM roofline: 16 C P bytes per
call (`bench/work.py`) at the chip's HBM peak, over the kernel's device
time in the trace.  The kernel's op is known by its outputs, (upload,
residual) blocks of (C, rows, 1024) floats and the (C, 1, 128) nonzero
counts; C, the call's cohort, is read from them.  Calls whose outputs
the compiler placed in on-chip memory (layout `S(1)`, as it does at a
cohort of 10) move no HBM bytes and are not counted."""

OP = (r"= \(f32\[(\d+),(\d+),1024\](\{[^}]*\}), f32\[\1,\2,1024\]\S*, "
      r"s32\[\1,1,128\]\S*\) custom-call\(")


def read(run):
    from bench import trace, work
    calls = [(m, s) for m, s in trace.matches(run.trace, OP)
             if "S(1)" not in m.group(3)]
    seconds = sum(s for _, s in calls)
    if not calls or seconds <= 0:
        return None
    nbytes = sum(work.upload_fused_bytes(int(m.group(1)), run.n_params)
                 for m, _ in calls)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds
