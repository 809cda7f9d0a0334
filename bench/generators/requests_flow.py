"""Flow sample requests (``"kind": "flow"``): an NFE budget drawn from
``budgets`` at exactly the shares ``budget_weights`` (in the ``order``
generator's order), and from the seed's generator ``cond_tokens``
conditioning token ids and the noise, ``positions`` latent positions of
the configuration's latent width."""
import numpy as np

from bench.loadgen import quota


def specs(req: dict, n: int, order, rng, c: dict) -> list[dict]:
    budgets = np.asarray(req["budgets"])[
        order.permutation(quota(n, req["budget_weights"]))]
    pos, cond = req["positions"], req["cond_tokens"]
    if cond != pos:
        raise ValueError("the flow head adds conditioning per position: "
                         "cond_tokens must equal positions")
    return [{"budget": int(b),
             "tokens": rng.integers(0, c["vocab"], size=cond, dtype=np.int32),
             "x0": rng.standard_normal((pos, c["latent_dim"]),
                                       dtype=np.float32)}
            for b in budgets]
