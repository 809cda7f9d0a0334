"""flow.host_tick_max_ms.throughput: ``flow.host_tick_max_ms`` (the
longest self time of one serving-thread tick, in ms) in a cell above the
knee, where it moves the samples completed a second and not a latency
tail."""
from bench.run import reader

read = reader("flow.host_tick_max_ms")
