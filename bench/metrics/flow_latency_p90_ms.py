"""flow_latency_p90_ms: 90th percentile over the window's requests of the
time from each request's scheduled send until its latents are on the
host (client clock). Failed requests count under ``failed``."""
from bench.readers import percentile


def read(run):
    lat = [(r["t_done"] - r["t_sched"]) * 1e3 for r in run["records"]
           if r.get("ok")]
    return percentile(lat, 90)
