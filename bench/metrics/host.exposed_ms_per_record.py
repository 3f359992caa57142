"""Device idle time the host stepper leaves exposed, per record: the idle
gaps of the first device in the traced window whose middle lies inside
one of the program's own host spans (``round``, ``stage.*``, ``py.gc``;
the innermost takes the gap), in ms over the ``round`` spans in the
window.  Idle under no program span (the benchmark's loop, its final
fence) is not counted.

Its reading also prints, as one JSON line on stderr, where a record's
device time and exposed idle went (`bench.stages.breakdown`)."""
import json
import sys


def read(run):
    from bench import stages
    rounds = stages.round_count(run.trace)
    if not rounds:
        return None
    print(json.dumps({"stages": stages.breakdown(run)}), file=sys.stderr,
          flush=True)
    exposed = stages.exposed_ns(run.trace)
    exposed.pop(stages.UNATTRIBUTED, None)
    return sum(exposed.values()) / 1e6 / rounds
