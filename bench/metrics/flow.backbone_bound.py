"""flow.backbone_bound: the least time of the backbone steps dispatched in
the window over the device time of the flow programs (``jit__extend``,
``jit__sample``), in percent.

The program counts each NFE step it dispatches under the number of real
rows that need it (registry ``forwards_by_rows{rows="<r>"}``; padded rows
never count). A step of r rows needs at least r rows of a guided step's
operations at the chip's peak (``work.flow_request_flops``) and at least
one read of the backbone's bf16 matrices at the chip's memory bandwidth,
whichever is longer: the weight read decides the steps of one row or
few. None without those counters or without device time."""
import re

from bench import work
from bench.readers import share

ROWS = re.compile(r'^forwards_by_rows\{rows="(\d+)"\}$')


def weight_bytes(c: dict) -> float:
    """Bytes of the backbone's matrices in bf16, computed from the tensor
    sizes: every block's attention and MLP matrices plus the latent input
    and output projections, read once per guided step (norm scales and
    the time embedding are left out, which only lowers the floor)."""
    return 2.0 * (c["n_layers"] * work.layer_params(c)
                  + 2 * c["latent_dim"] * c["d_model"])


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
    c, peaks = run["model"].c, run["peaks"]
    req, srv = run["mix"]["requests"], run["mix"]["server"]
    row_s = work.flow_request_flops(c, 1, req["positions"],
                                    srv["cfg_scale"] != 0.0) \
        / peaks["bf16_flop_per_s"]
    weight_s = weight_bytes(c) / peaks["hbm_bytes_per_s"]
    least = sum(n * max(r * row_s, weight_s) for r, n in steps.items())
    return share(least, tr["programs"]["flow"])
