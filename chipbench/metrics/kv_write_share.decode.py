"""Model step: device self time of the ops under the program's kv_write
scope (every write into the paged KV pool) over device busy time in the
traced window, in % (progtrace).  None where the trace holds no op-name
paths."""
import progtrace


def read(rec):
    tr = rec.get("trace")
    return None if tr is None else progtrace.scope_share(tr, "kv_write")
