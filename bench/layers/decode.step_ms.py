"""Mean device time of the decode-step program (``_decode_impl``) over its
runs in the traced window, from its first operation to its last."""
import trace_reduce as TR


def read(view):
    runs = TR.module_runs(view["trace"], "_decode_impl")
    if not runs:
        return None
    return 1e3 * sum(r.end - r.start for r in runs) * 1e-9 / len(runs)
