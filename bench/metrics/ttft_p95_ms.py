"""95th percentile, over every request scheduled inside the window, of the
time from its scheduled send time to the stamp of its first token.  A
request with no token by the window's close counts its wait so far."""
from harness.readings import percentile


def read(r):
    w = r.window
    waits = []
    for tk in w.tracks:
        if w.t0 <= tk.sched_t < w.t1:
            first = tk.stamps[0] if tk.stamps else w.t1
            waits.append((min(first, w.t1) - tk.sched_t) * 1e3)
    return percentile(waits, 95)
