"""flow.host_busy.throughput: ``flow.host_busy`` (the serving thread's
own work over the profiled interval, in percent) in a cell above the
knee, where it moves the samples completed a second and not a latency
tail."""
from bench.run import reader

read = reader("flow.host_busy")
