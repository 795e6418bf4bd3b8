"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of operation intervals) / window."""
import trace_reduce as TR


def read(view):
    red = view["trace"]
    return 100.0 * (1.0 - TR.busy_s(red) / red.window_s)
