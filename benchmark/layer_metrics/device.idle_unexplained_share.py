"""Of device 0's idle time in the traced window outside runs of a program,
the share that lies under no top-level program span of the trainer's thread
(spans widened by the offset between the host's and the device's clock):
idle time the program's own timeline cannot put a name to. Idle time is what
lies between two recorded operations of the device; the ends of the window
that the device's tracer did not see are not counted."""

from benchmark.reduce import host as hr

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "fit_tokens_per_s"


def read(art):
    host, trace = hr.of(art), art.get("trace")
    share = hr.idle_unexplained_share(host, trace) if host and trace else None
    return None if share is None else 100.0 * share
