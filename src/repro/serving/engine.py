"""Serving engines.

``FlowSampler`` — the paper's product: BNS-accelerated batched sampling of a
flow model (any backbone in the zoo). A thin jit'd session over Algorithm 1:
construct it from a serialized ``SolverArtifact`` (``from_artifact``) or any
NS solver, and each request batch costs exactly ``n`` backbone forwards.

``AnytimeFlowSampler`` — multi-NFE anytime serving from ONE artifact.

Budget-routing contract: the sampler owns the anytime solver's served
``budgets``; a request asks for an NFE budget and is routed as follows.

  * ``sample(batch, key, budget=m)`` with ``m`` in ``budgets`` runs the
    extracted m-step early-exit solver (``core.anytime.extract_ns``) — a
    batch of requests at budget m costs exactly m backbone forwards, and the
    jit'd program for each budget is compiled once and cached.
  * ``m`` not in ``budgets``: ``resolve_budget(m)`` picks the nearest served
    budget (ties to the smaller, i.e. cheaper); ``strict=True`` raises
    instead. Callers that must not silently change NFE (``launch/serve.py
    --strict-nfe``) pass strict.
  * ``sample_all(batch, key)`` runs the one shared trajectory to the top
    budget and emits every early exit — max(budgets) forwards total for all
    budgets at once (mixed-budget batches, evaluation).

``DecodeEngine`` — batched autoregressive decode with KV cache / recurrent
state (the ``serve_step`` the decode dry-run shapes lower). ``greedy`` is a
jit'd ``lax.scan`` multi-token program; the slot API (``init_slot_state`` /
``step_slots`` / ``reset_slots`` / ``prefill_slots``) serves independent
sequences from the rows of one fixed-slot batched state — the substrate of
the decode-side continuous-batching gateway
(``repro.serving.decode.DecodeGateway``). ``page_size > 0`` switches the
KV-cache families to a PAGED state (``PagedKVCache``: shared page pool +
per-row block table, vLLM-style), and ``SamplingParams`` /
``sample_tokens`` add temperature / top-k / top-p sampling beside greedy.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import anytime as anytime_mod
from repro.core import ns_solver
from repro.core.ns_solver import NSParams
from repro.core.schedulers import Scheduler
from repro.models import model as M

Array = jax.Array


def nearest_budget(budgets, m: int, strict: bool = False) -> int:
    """THE budget-routing rule, shared by every serving surface: an exact
    match passes through; otherwise the nearest served budget (ties break
    to the smaller — fewer backbone forwards), or ``ValueError`` under
    ``strict``."""
    if m in budgets:
        return m
    if strict:
        raise ValueError(f"budget {m} not served; have {tuple(budgets)}")
    return min(budgets, key=lambda b: (abs(b - m), b))


def nearest_latent_tokens(params: dict, latents: Array) -> Array:
    """Decode sampled latents to tokens by nearest latent embedding."""
    table = params["flow"]["latent_embed"].astype(jnp.float32)
    d2 = (jnp.sum(latents.astype(jnp.float32) ** 2, -1, keepdims=True)
          - 2.0 * latents.astype(jnp.float32) @ table.T
          + jnp.sum(table**2, -1))
    return jnp.argmin(d2, axis=-1)


@dataclasses.dataclass
class FlowSampler:
    params: dict
    cfg: ModelConfig
    sched: Scheduler
    solver: NSParams
    cfg_scale: float = 0.0
    update_fn: Optional[Callable] = None   # e.g. kernels.ns_update make_update_fn

    def __post_init__(self):
        def _sample(params, solver, batch, x0):
            field = M.velocity_field(params, self.cfg, self.sched, batch,
                                     cfg_scale=self.cfg_scale)
            return ns_solver.ns_sample(solver, field.fn, x0,
                                       update_fn=self.update_fn)

        self._sample = jax.jit(_sample)

    @classmethod
    def from_artifact(cls, artifact, *, params: dict, cfg: ModelConfig,
                      sched: Scheduler, budget: Optional[int] = None,
                      update_fn: Optional[Callable] = None) -> "FlowSampler":
        """Serving session from a loaded ``repro.solvers.SolverArtifact``.

        The artifact carries the solver parameters and the CFG scale it was
        distilled under; the backbone (params/cfg/sched) is supplied by the
        launcher. ``budget`` selects one early exit of an anytime artifact
        (required there — use ``AnytimeFlowSampler`` to serve them all).
        """
        if budget is None and artifact.kind == "anytime":
            raise TypeError(
                "anytime artifacts serve several budgets; pass budget=m for "
                "a fixed-NFE session or use AnytimeFlowSampler.from_artifact")
        solver = (artifact.ns_params if budget is None
                  else artifact.ns_at_budget(budget))
        return cls(params=params, cfg=cfg, sched=sched, solver=solver,
                   cfg_scale=artifact.spec.cfg_scale, update_fn=update_fn)

    # -- budget protocol (shared with AnytimeFlowSampler, used by the
    #    gateway): a fixed-NFE session serves exactly one budget. -----------

    @property
    def budgets(self) -> tuple[int, ...]:
        return (self.solver.n,)

    def resolve_budget(self, m: int, strict: bool = False) -> int:
        """One served budget: exact match or (with ``strict``) rejection."""
        if m != self.solver.n and strict:
            raise ValueError(f"budget {m} not served; have {self.budgets}")
        return self.solver.n

    def sample_from(self, batch: Optional[dict], x0: Array,
                    budget: Optional[int] = None) -> Array:
        """Integrate given noise ``x0`` (this session's one budget)."""
        if budget is not None and budget != self.solver.n:
            raise ValueError(f"budget {budget} not served; have {self.budgets}")
        return self._sample(self.params, self.solver, batch, x0)

    def sample(self, batch: dict, key: Array) -> Array:
        """Generate latent sequences conditioned on ``batch`` tokens.

        The latent length equals the conditioning token length — the backbone
        adds conditioning embeddings position-wise, so they cannot differ.
        """
        B, S = batch["tokens"].shape
        x0 = jax.random.normal(key, (B, S, self.cfg.latent_dim))
        return self._sample(self.params, self.solver, batch, x0)

    def nearest_tokens(self, latents: Array) -> Array:
        """Decode sampled latents to tokens by nearest latent embedding."""
        return nearest_latent_tokens(self.params, latents)


@dataclasses.dataclass
class AnytimeFlowSampler:
    """Budget-aware serving session over ONE anytime solver artifact.

    See the module docstring for the budget-routing contract. Per-budget
    jit'd programs are compiled lazily and cached, so a running server pays
    one compile per distinct budget, then exactly m forwards per request.
    """

    params: dict
    cfg: ModelConfig
    sched: Scheduler
    anytime: anytime_mod.AnytimeParams
    budgets: tuple[int, ...]
    cfg_scale: float = 0.0
    update_fn: Optional[Callable] = None   # e.g. kernels.ns_update make_update_fn

    def __post_init__(self):
        self.budgets = tuple(sorted(self.budgets))
        self._per_budget: dict[int, Callable] = {}
        self._all: Optional[Callable] = None
        self._extends: dict[tuple[int, int], Callable] = {}

    @classmethod
    def from_artifact(cls, artifact, *, params: dict, cfg: ModelConfig,
                      sched: Scheduler,
                      update_fn: Optional[Callable] = None
                      ) -> "AnytimeFlowSampler":
        """Serving session from a loaded anytime ``SolverArtifact``."""
        if artifact.kind != "anytime":
            raise TypeError(f"{artifact.kind!r} artifacts serve one budget; "
                            "use FlowSampler.from_artifact")
        return cls(params=params, cfg=cfg, sched=sched,
                   anytime=artifact.params, budgets=artifact.budgets,
                   cfg_scale=artifact.spec.cfg_scale, update_fn=update_fn)

    def _field(self, batch: dict):
        return M.velocity_field(self.params, self.cfg, self.sched, batch,
                                cfg_scale=self.cfg_scale)

    def resolve_budget(self, m: int, strict: bool = False) -> int:
        """Route a requested NFE to a served budget (nearest; ties cheaper)."""
        return nearest_budget(self.budgets, m, strict)

    def ns_at_budget(self, m: int) -> NSParams:
        return anytime_mod.extract_ns(self.anytime, self.budgets, m)

    def sample_from(self, batch: dict, x0: Array, budget: int) -> Array:
        """Integrate given noise ``x0`` at exactly ``budget`` NFE."""
        return self._at_budget(budget)(self.params, batch, x0)

    def _at_budget(self, budget: int) -> Callable:
        """The jitted program of ``budget`` (one per budget)."""
        fn = self._per_budget.get(budget)
        if fn is None:
            ns = self.ns_at_budget(budget)   # raises on unserved budgets

            def _sample(params, batch, x0, ns=ns):
                field = M.velocity_field(params, self.cfg, self.sched, batch,
                                         cfg_scale=self.cfg_scale)
                return ns_solver.ns_sample(ns, field.fn, x0,
                                           update_fn=self.update_fn)

            fn = self._per_budget[budget] = jax.jit(_sample)
        return fn

    def sample(self, batch: dict, key: Array, budget: int,
               strict: bool = False) -> Array:
        """Generate latents for ``batch`` at the requested NFE budget."""
        budget = self.resolve_budget(budget, strict=strict)
        B, S = batch["tokens"].shape
        x0 = jax.random.normal(key, (B, S, self.cfg.latent_dim))
        return self.sample_from(batch, x0, budget)

    def sample_all_from(self, batch: dict, x0: Array) -> dict[int, Array]:
        """One shared trajectory from ``x0``; every budget's output, at
        max(budgets) total forwards."""
        if self._all is None:
            def _sample(params, batch, x0):
                field = M.velocity_field(params, self.cfg, self.sched, batch,
                                         cfg_scale=self.cfg_scale)
                return anytime_mod.anytime_sample(self.anytime, self.budgets,
                                                  field.fn, x0,
                                                  update_fn=self.update_fn)

            self._all = jax.jit(_sample)
        return self._all(self.params, batch, x0)

    def sample_all(self, batch: dict, key: Array) -> dict[int, Array]:
        B, S = batch["tokens"].shape
        x0 = jax.random.normal(key, (B, S, self.cfg.latent_dim))
        return self.sample_all_from(batch, x0)

    # -- carry protocol (continuous batching, repro.serving.continuous) ------

    def carry_start(self, batch: Optional[dict],
                    x0: Array) -> anytime_mod.AnytimeCarry:
        """A fresh shared-trajectory carry over ``x0`` (no forwards spent)."""
        return anytime_mod.anytime_carry(self.anytime, self.budgets, x0)

    def carry_extend(self, batch: Optional[dict],
                     carry: anytime_mod.AnytimeCarry, stop: int
                     ) -> tuple[anytime_mod.AnytimeCarry, dict[int, Array]]:
        """Advance the shared trajectory to ``stop`` evals; returns the new
        carry plus the early-exit outputs crossed on the way.

        Costs exactly ``stop - carry.step`` backbone forwards for the whole
        slot batch. One jit program per (start, stop) leg — the boundary
        pairs a trajectory can traverse are few and fixed, so a running
        server compiles each leg once (mirroring the per-budget programs).

        The returned exits dict is also the STREAMING surface: row i of
        ``exits[k]`` is exactly the sample a budget-k request with slot
        i's noise would have received (the anytime grid is nested), so
        ``ContinuousGateway`` forwards it to streaming clients as a valid
        intermediate sample at zero extra forwards — and because the
        carry's per-row columns fully determine the remaining trajectory,
        the same property makes exit boundaries free preemption points
        (``serving.slo.PausedCarry``).

        A carry that names ``rows`` (int32, w distinct slot indices) runs
        the leg at width w on those slots alone, in the same program: it
        gathers their ``x0``, ``U``, ``x`` and conditioning, writes ``U``
        and ``x`` back into the full carry, and returns exits w wide (exit
        row j is slot ``rows[j]``; ``anytime_extend``). One program per
        (leg, w).
        """
        narrow = () if carry.rows is None else (carry.rows,)
        U, x, exits = self._leg(carry.step, stop)(
            self.params, batch, carry.x0, carry.U, carry.x, *narrow)
        return anytime_mod.AnytimeCarry(x0=carry.x0, U=U, x=x,
                                        step=stop), exits

    def _leg(self, start: int, stop: int) -> Callable:
        """The jitted program of leg ``start..stop`` (one per leg; jit
        specialises it per width)."""
        fn = self._extends.get((start, stop))
        if fn is None:
            def _extend(params, batch, x0, U, x, rows=None):
                if rows is not None and batch is not None:
                    batch = jax.tree.map(lambda a: a[rows], batch)
                field = M.velocity_field(params, self.cfg, self.sched, batch,
                                         cfg_scale=self.cfg_scale)
                c = anytime_mod.AnytimeCarry(x0=x0, U=U, x=x, step=start,
                                             rows=rows)
                out, exits = anytime_mod.anytime_extend(
                    self.anytime, self.budgets, field.fn, c, stop,
                    update_fn=self.update_fn)
                return out.U, out.x, exits

            fn = self._extends[(start, stop)] = jax.jit(_extend)
        return fn

    def carry_warm(self, batch: Optional[dict],
                   carry: anytime_mod.AnytimeCarry, stop: int) -> None:
        """Compile the (leg, width) program of ``carry_extend`` before
        traffic needs it, by running it once on ``carry`` and dropping
        what it computes."""
        self.carry_extend(batch, carry, stop)

    def nearest_tokens(self, latents: Array) -> Array:
        """Decode sampled latents to tokens by nearest latent embedding."""
        return nearest_latent_tokens(self.params, latents)


# ---------------------------------------------------------------------------
# Sampling (temperature / top-k / top-p beside greedy)
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs. ``temperature == 0`` is exact greedy
    (argmax); ``top_k == 0`` and ``top_p == 1.0`` disable those filters.
    Determinism contract: given the gateway's base key, a request's tokens
    depend only on (base key, request uid, step) — reproducible across
    restarts and fleet re-routing."""

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


class SlotSampling(NamedTuple):
    """Batched per-slot sampling state fed to the sampled step program.
    ``keys`` are per-SEQUENCE keys (base key folded with the request uid);
    ``counts`` is each row's emitted-token count, folded in per step so every
    position draws fresh randomness without host-side key churn."""

    keys: Array      # (slots, 2) uint32 per-sequence PRNG keys
    counts: Array    # (slots,) int32 tokens emitted so far
    temps: Array     # (slots,) f32 temperature (0 = greedy)
    top_ks: Array    # (slots,) int32 top-k cutoff (0 = off)
    top_ps: Array    # (slots,) f32 top-p cutoff (1.0 = off)


def sample_tokens(logits: Array, keys: Array, temps: Array, top_ks: Array,
                  top_ps: Array) -> Array:
    """Vectorised per-row sampling: temperature scale, top-k and top-p
    truncation, Gumbel-max draw; rows with ``temps == 0`` take the exact
    argmax. All filters run on the descending-sorted logits so the k-th
    largest value and the nucleus boundary are O(V log V) with no scatters.
    """
    V = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    scaled = lf / jnp.maximum(temps, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    # top-k threshold: the k-th largest scaled logit (k == 0 -> keep all)
    k = jnp.clip(jnp.where(top_ks > 0, top_ks, V), 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    # top-p threshold over the sorted distribution; the exclusive cumsum
    # guarantees the top-1 token always survives
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    in_nucleus = (cum - probs) < top_ps[:, None]
    pth = jnp.min(jnp.where(in_nucleus, sorted_desc, jnp.inf), axis=-1,
                  keepdims=True)
    cutoff = jnp.maximum(kth, pth)
    masked = jnp.where(scaled >= cutoff, scaled, _NEG_INF)
    gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (V,)))(keys)
    sampled = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


@dataclasses.dataclass
class DecodeEngine:
    """Batched autoregressive decode with KV cache / recurrent state.

    Two serving surfaces:

    * ``greedy(prompt, state, num_steps)`` — run-to-completion batched
      decode: one jit'd ``lax.scan`` program per ``num_steps``, compiled
      once and cached (the old host-side per-token Python loop paid a
      device dispatch round-trip per token).
    * slot serving — ``init_slot_state`` builds a fixed-slot batched state
      whose rows are INDEPENDENT sequences at their own decode positions
      (per-row ``index`` vector); ``step_slots`` advances only the rows
      picked by the active mask (write-masked state update) and
      ``reset_slots`` re-zeroes freed rows for the next admission. Rows
      are independent through the backbone, so a slot's tokens are
      bit-identical to decoding its sequence alone (MoE: in the
      no-capacity-drop regime, as for batched decode generally). This is
      the substrate of ``repro.serving.decode.DecodeGateway``.

    ``page_size > 0`` pages the slot state for the KV-cache families: the
    cache becomes a shared ``(L, num_pages, KV, page_size, hd)`` pool plus a
    per-row block table (``PagedKVCache``). Page ownership replaces row
    masking for the pool leaves — a masked-off row's in-flight write lands in
    its own pages (overwritten before the row is next read) or in the
    reserved trash page 0 (freed rows), so ``step_slots`` takes the new pool
    unconditionally and ``reset_slots`` never zeroes it. The ``ssm`` family
    accepts ``page_size`` as a no-op (its recurrent state is already O(1)
    per slot); hybrid/encdec reject it.
    """

    params: dict
    cfg: ModelConfig
    window: int = 0
    page_size: int = 0        # > 0: paged KV cache (KV families; ssm no-op)
    paged_kernel: bool = False  # paged attention via the Pallas kernel

    #: gateways probe this before routing sampled requests (toy engines
    #: and older engines are greedy-only).
    supports_sampling = True

    def __post_init__(self):
        if self.page_size:
            if self.window:
                raise ValueError(
                    "paged KV cache is incompatible with sliding-window "
                    "decode (the ring buffer already bounds resident KV)")
            if self.cfg.family not in M.PAGED_FAMILIES + ("ssm",):
                raise TypeError(
                    f"page_size set but family {self.cfg.family!r} has no "
                    f"pageable KV state (pageable: {M.PAGED_FAMILIES}; "
                    "ssm accepted as a no-op)")

        def _step(params, token, state):
            return M.decode_apply(params, self.cfg, token, state,
                                  window=self.window,
                                  paged_kernel=self.paged_kernel)

        self._step = jax.jit(_step)
        self._greedy_fns: dict[int, Callable] = {}
        self._prefill_fns: dict[int, Callable] = {}

        axes = M.decode_state_batch_axes(self.cfg, paged=self.paged)

        def _mask_rows(mask, new, old):
            """Per-leaf row select: ``mask`` picks rows (along each leaf's
            batch axis) that take ``new``; other rows keep ``old``. Leaves
            whose axis reads ``-1`` (the shared page pool) take ``new``
            unconditionally — isolation there is by page ownership, not by
            row masking (see class docstring)."""

            def keep(ax, n, o):
                if ax == -1:
                    return n
                shape = [1] * n.ndim
                shape[ax] = mask.shape[0]
                return jnp.where(mask.reshape(shape), n, o)

            return jax.tree.map(keep, axes, new, old)

        self._mask_rows_fn = _mask_rows

        def _step_slots(params, token, state, active):
            logits, new = M.decode_apply(params, self.cfg, token, state,
                                         window=self.window,
                                         paged_kernel=self.paged_kernel)
            state = _mask_rows(active, new, state)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), state

        self._step_slots = jax.jit(_step_slots)

        def _step_slots_sampled(params, token, state, active, keys, counts,
                                temps, top_ks, top_ps):
            logits, new = M.decode_apply(params, self.cfg, token, state,
                                         window=self.window,
                                         paged_kernel=self.paged_kernel)
            state = _mask_rows(active, new, state)
            step_keys = jax.vmap(jax.random.fold_in)(keys, counts)
            toks = sample_tokens(logits, step_keys, temps, top_ks, top_ps)
            return toks, state

        self._step_slots_sampled = jax.jit(_step_slots_sampled)

        def _reset_slots(state, free):
            """Zero the rows where ``free`` is True — except the shared page
            pool (axis ``-1``), which other rows' live pages make
            untouchable; freed rows are isolated by their zeroed block
            table (trash page 0) instead."""

            def keep(ax, o):
                if ax == -1:
                    return o
                shape = [1] * o.ndim
                shape[ax] = free.shape[0]
                return jnp.where(free.reshape(shape), jnp.zeros_like(o), o)

            return jax.tree.map(keep, axes, state)

        self._reset_slots = jax.jit(_reset_slots)

    def init_state(self, batch: int, slots: int, dtype=jnp.float32):
        return M.init_decode_state(self.cfg, batch, slots, dtype)

    @property
    def paged(self) -> bool:
        """True when slot state is a ``PagedKVCache`` (page_size set AND the
        family has pageable KV; ssm keeps its dense recurrent state)."""
        return self.page_size > 0 and self.cfg.family in M.PAGED_FAMILIES

    @property
    def seq_capacity_bounded(self) -> bool:
        """True when decode positions must fit the cache's physical slots:
        the non-windowed KV-cache families silently clamp writes to the
        last slot past capacity (degraded tokens, no error). Sliding-window
        ring buffers and pure recurrent state decode unbounded lengths."""
        return self.window == 0 and self.cfg.family != "ssm"

    def step(self, token: Array, state):
        """One batched decode step: token (B,) -> (logits (B, V), state)."""
        return self._step(self.params, token, state)

    def greedy(self, prompt: Array, state, num_steps: int) -> tuple[Array, object]:
        """prompt: (B,) last prompt token. Returns (B, num_steps) tokens.

        The whole multi-token loop is ONE jit'd ``lax.scan`` program per
        ``num_steps`` (cached), so a serving session pays one compile and
        then zero host round-trips inside the decode loop.
        """
        fn = self._greedy_fns.get(num_steps)
        if fn is None:
            def _greedy(params, token, state):
                def body(carry, _):
                    token, state = carry
                    logits, state = M.decode_apply(params, self.cfg, token,
                                                   state, window=self.window)
                    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (token, state), token

                (_, state), toks = jax.lax.scan(body, (token, state), None,
                                                length=num_steps)
                return jnp.swapaxes(toks, 0, 1), state

            fn = self._greedy_fns[num_steps] = jax.jit(_greedy)
        return fn(self.params, prompt, state)

    # -- slot serving (decode-side continuous batching) ----------------------

    def init_slot_state(self, slots: int, cache_slots: int,
                        dtype=jnp.float32,
                        total_pages: Optional[int] = None):
        """Fixed-slot batched decode state with PER-ROW positions: row i
        serves an independent sequence; ``index`` is a (slots,) vector so
        sequences admitted at different times sit at different positions.

        Paged engines return a ``PagedKVCache`` instead: a shared pool of
        ``total_pages`` pages (default: page 0 as trash + every slot at full
        ``cache_slots`` residency — shrink it to overcommit) and an all-zero
        block table awaiting the gateway's allocator. ``cache_slots`` must be
        a multiple of ``page_size`` (it fixes the block-table width, and the
        dense-gather fallback is bit-identical to the dense cache only when
        the gathered length matches)."""
        if self.paged:
            ps = self.page_size
            if cache_slots % ps:
                raise ValueError(
                    f"cache_slots ({cache_slots}) must be a multiple of "
                    f"page_size ({ps})")
            blocks = cache_slots // ps
            pages = (1 + slots * blocks) if total_pages is None else total_pages
            if pages < 2:
                raise ValueError("total_pages must be >= 2 (page 0 is the "
                                 "reserved trash page)")
            return M.init_paged_decode_state(self.cfg, slots, pages, ps,
                                             blocks, dtype)
        state = M.init_decode_state(self.cfg, slots, cache_slots, dtype)
        return state._replace(index=jnp.zeros((slots,), jnp.int32))

    def step_slots(self, token: Array, state, active: Array,
                   sampling: Optional[SlotSampling] = None):
        """One write-masked decode step over the slot batch.

        ``token`` (slots,) feeds each row; rows where ``active`` is False
        still flow through the backbone (fixed batch shape — one compiled
        program regardless of occupancy) but their state rows and positions
        are left untouched. Returns (next token (slots,), state): greedy
        argmax, or per-row ``SlotSampling`` draws when ``sampling`` is given
        (rows with temperature 0 stay exact greedy, so mixed batches cost
        one program)."""
        if sampling is None:
            return self._step_slots(self.params, token, state, active)
        return self._step_slots_sampled(self.params, token, state, active,
                                        *sampling)

    def prefill_slots(self, tokens: Array, lengths: Array, state, mask: Array):
        """Batched chunked prefill: feed ``tokens`` (slots, C) teacher-forced
        into the rows where ``mask`` is True, row i consuming its first
        ``lengths[i]`` columns (the rest are padding). One jit'd scan program
        per chunk width C, shared by every prompt; logits are discarded. The
        scan body is the same ``decode_apply`` as ``step_slots``, so prefill
        state is bit-identical to feeding the prompt token-by-token."""
        C = int(tokens.shape[1])
        fn = self._prefill_fns.get(C)
        if fn is None:
            def _prefill(params, tokens, lengths, state, mask):
                def body(state, t):
                    tok = jnp.take(tokens, t, axis=1)
                    act = mask & (t < lengths)
                    _, new = M.decode_apply(params, self.cfg, tok, state,
                                            window=self.window,
                                            paged_kernel=self.paged_kernel)
                    return self._mask_rows_fn(act, new, state), None

                state, _ = jax.lax.scan(body, state, jnp.arange(C))
                return state

            fn = self._prefill_fns[C] = jax.jit(_prefill)
        return fn(self.params, tokens, lengths, state, mask)

    def reset_slots(self, state, free: Array):
        """Scatter a fresh zero state into the rows where ``free`` is True
        (``init_decode_state`` is all-zeros), readying them for admission
        of a new sequence at position 0. Paged: zeroes the freed rows'
        block-table entries (-> trash page 0) and positions but leaves the
        shared pool alone."""
        return self._reset_slots(state, free)

    def with_block_table(self, state, table) -> object:
        """Swap in the gateway allocator's host-side block table (paged
        engines only). ``table`` is (slots, blocks_per_slot) page ids."""
        return state._replace(block_table=jnp.asarray(table, jnp.int32))


def greedy_demo(engine: DecodeEngine, batch: int, steps: int,
                cache_slots: int, prompt: Optional[Array] = None
                ) -> tuple[Array, float]:
    """Shared solo-decode demo loop (``launch/serve.py --mode decode`` and
    ``examples/serve_decode.py`` previously each had their own copy): fresh
    state, ``steps`` greedy tokens, returns (tokens, ms_per_token)."""
    state = engine.init_state(batch, cache_slots)
    if prompt is None:
        prompt = jnp.zeros((batch,), jnp.int32)
    t0 = time.time()
    tokens, _ = engine.greedy(prompt, state, steps)
    jax.block_until_ready(tokens)
    dt_ms = (time.time() - t0) / steps * 1e3
    return tokens, dt_ms
