"""flow_samples_per_s: samples completed inside the window over the
window's seconds."""
from bench.readers import in_window


def read(run):
    n = sum(1 for r in run["records"]
            if r.get("ok") and in_window(run, r["t_done"]))
    return n / run["window_s"] if n else None
