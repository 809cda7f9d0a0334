"""flow.backbone_bound: the least time of the backbone steps dispatched in
the window over the device time of the flow programs (``jit__extend``,
``jit__sample``), in percent.

The program counts each NFE step it dispatches under the number of real
rows that need it (registry ``forwards_by_rows{rows="<r>"}``; padded rows
never count). A step of r rows needs at least r rows of a guided step's
operations at the chip's peak (``work.flow_request_flops``) and at least
one read of the matrices a forward over its tokens must touch
(``work.flow_weight_bytes`` over r rows of the mix's positions, both
guidance branches: the configuration's block module decides what a step
of few tokens reads) at the chip's memory bandwidth, whichever is longer:
the weight read decides the steps of one row or few. None without those
counters or without device time."""
import re

from bench import work
from bench.readers import share

ROWS = re.compile(r'^forwards_by_rows\{rows="(\d+)"\}$')


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    steps = {}
    for key, n in run["counters"].items():
        m = ROWS.match(key)
        if m and n > 0:
            steps[int(m.group(1))] = n
    if not steps:
        return None
    model, peaks = run["model"], run["peaks"]
    req, srv = run["mix"]["requests"], run["mix"]["server"]
    guided = srv["cfg_scale"] != 0.0
    row_s = work.flow_request_flops(model, 1, req["positions"], guided) \
        / peaks["bf16_flop_per_s"]

    def weight_s(r):
        tokens = r * req["positions"] * (2 if guided else 1)
        return work.flow_weight_bytes(model, tokens) \
            / peaks["hbm_bytes_per_s"]

    least = sum(n * max(r * row_s, weight_s(r)) for r, n in steps.items())
    return share(least, tr["programs"]["flow"])
