"""flow.backbone_roofline: the least time of the backbone work the
window's requests got inside the window (``readers.flow_row_steps``: one
request row per NFE step, two forwards of operations under guidance, at
the chip's peak; ``work.flow_request_flops``) over the device time of the
flow programs (``jit__extend``, ``jit__sample``), in percent. Padded rows
count as no work, so padding lowers the share. The weight read that bounds
a step of one or two rows is left out (the spans do not say how many real
rows a dispatch held), which can only lower the share."""
from bench import work
from bench.readers import flow_row_steps, share


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    req, srv = run["mix"]["requests"], run["mix"]["server"]
    flops = flow_row_steps(run) * work.flow_request_flops(
        run["model"], 1, req["positions"], srv["cfg_scale"] != 0.0)
    return share(flops / run["peaks"]["bf16_flop_per_s"],
                 tr["programs"]["flow"])
