"""Share of the entry's calls that the host spent outside the program's
execution: ``1 - sum(*.execute) / sum(root calls)`` over the window's
jobs, read from the spans the program records in its process tracer
(``repro.telemetry.default_tracer``).  Nothing calls the program between
the window and the readers, so the window's jobs are the last root
calls.  Nothing to read where the program records no such spans."""

UNIT = "fraction"


def read(run):
    from repro import telemetry

    default_tracer = getattr(telemetry, "default_tracer", None)
    if default_tracer is None or not run.jobs:
        return None
    events = list(default_tracer().events)
    roots = [e for e in events if e.parent_id is None]
    if len(roots) < len(run.jobs):
        return None
    roots = roots[-len(run.jobs):]
    ids = {e.span_id for e in roots}
    total = sum(e.dur for e in roots)
    execute = sum(e.dur for e in events
                  if e.root_id in ids and e.name.endswith(".execute"))
    return 1.0 - execute / total if total > 0 else None
