"""Scheduler and block pool: the serving thread's own time per dispatch in
the traced window, in ms: the summed self time of the program's
serve.plan, serve.blocks, dispatch and serve.retire spans over the number
of dispatch spans that start in the window (progtrace).  None where the
trace holds no program spans."""
import progtrace


def read(rec):
    tr = rec.get("trace")
    return None if tr is None else progtrace.host_ms_per_dispatch(tr)
