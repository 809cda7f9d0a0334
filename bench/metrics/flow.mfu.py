"""flow.mfu: operations the samples completed in the window needed
(budget x two guided forwards x the real positions, no padding;
``work.flow_request_flops``) over the window x the chip's peak,
in percent."""
from bench import work
from bench.readers import in_window


def read(run):
    req, srv = run["mix"]["requests"], run["mix"]["server"]
    guided = srv["cfg_scale"] != 0.0
    flops = sum(work.flow_request_flops(run["model"], r["spec"]["budget"],
                                        req["positions"], guided)
                for r in run["records"]
                if r.get("ok") and in_window(run, r["t_done"]))
    if not flops:
        return None
    return 100.0 * flops / (run["window_s"] * run["peaks"]["bf16_flop_per_s"])
