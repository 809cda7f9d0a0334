"""Continuous batching: admit requests into in-flight anytime trajectories.

The flush-only gateway (``repro.serving.gateway``) exploits the anytime
solver's shared trajectory only at flush time: a request arriving one tick
after a flush waits a full ``max_wait_ms`` even though an in-flight
trajectory is passing an exit boundary it could join. This module turns the
solver's nested-grid structure into request-level continuous batching:

* An in-flight anytime dispatch is tracked as a SEQUENCE OF EXIT-BOUNDARY
  JOIN POINTS — the trajectory advances leg by leg between consecutive
  served budgets, returning control to the host at every boundary.
* At each boundary k the engine RELEASES the slots whose served budget is k
  (their early-exit output resolves the future immediately) and ADMITS
  queued requests with budget > k into the freed slots: a joiner's prefix
  ``0..k`` is computed from its OWN noise via the shared intermediate
  coefficients (the first k rows of the extracted ``ns_at_budget`` solver),
  then steps ``k..b`` ride the shared grid with the rest of the batch.
* Every served sample stays bit-identical to the direct sampler with the
  same noise — see the exit-boundary join invariant on
  ``core.anytime.anytime_extend`` — and a joined request at budget b adds at
  most b incremental backbone forwards (k for the prefix dispatch; the
  shared legs are already being paid for).

``ContinuousScheduler`` extends ``BatchScheduler`` with slot admission and
release planning (pure functions of pending + now — fake-clock testable);
``ContinuousGateway.pump`` interleaves trajectory legs, joins, and the
inherited flush planning, so requests that cannot join (budget at or below
the next boundary, or no free slot) still flush under the usual
max-batch/max-wait rules. ``stats()`` additionally reports join-rate and
slot-occupancy.

Shape tiers (``repro.serving.tiers``): with a ``ShapeLadder`` configured,
``shape_key`` holds the tier-padded shape, so trajectories are PER-TIER,
not per-exact-shape — a joiner of any native shape in the tier rides the
shared ``AnytimeCarry`` through its zero-padded position rows, and every
release/partial crops back to the entry's ``native_shape`` before the
caller sees it (bit-identical to the direct sampler at the native shape).

Samplers must speak the carry protocol on top of the budget protocol:
``carry_start(batch, x0)``, ``carry_extend(batch, carry, stop)`` and
``carry_warm(batch, carry, stop)`` (``AnytimeFlowSampler`` jit-caches one
program per (start, stop) leg and width). With ``mesh=``
the carry arrays are re-placed on the serving mesh after every leg and
join scatter (``sharded.carry_placer``).

Leg width: the carry is always ``max_slots`` wide, but each leg runs over
the live slots alone (the carry's ``rows``), padded with free
slots to the join ladder's power of two, no narrower than an eighth of
``max_slots`` or the mesh's data-axis size: a trajectory with 3 of 16
slots live pays for 4 rows a step, not 16. The first trajectory of each
shape warms every narrow (leg, width) program through ``carry_warm``, so
traffic compiles none.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.observability import profile_span
from repro.serving.gateway import (
    BatchScheduler,
    Gateway,
    Response,
    _Entry,
    assemble_rows,
)
from repro.serving.slo import PausedCarry, is_urgent, urgency_key
from repro.serving.tiers import crop_row


class ContinuousScheduler(BatchScheduler):
    """Slot admission/release planning on top of flush planning.

    ``plan_start`` decides when pending requests open a new trajectory;
    ``plan_joins`` decides which requests are admitted into an in-flight one
    at an exit boundary. Both are pure functions of (pending, now, slot
    state) — the unit tests drive them with a fake clock and assert the
    exact slate. The inherited ``plan`` keeps serving whatever cannot ride
    a trajectory.
    """

    def __init__(self, max_slots: int = 8, boundaries: Sequence[int] = (),
                 max_batch: Optional[int] = None, max_wait_ms: float = 10.0,
                 policy: str = "auto", can_mix: bool = False,
                 top_budget: Optional[int] = None,
                 max_leg: Optional[int] = None,
                 join_cost_cap: float = 0.5, slo_aware: bool = False):
        super().__init__(max_batch=max_batch or max_slots,
                         max_wait_ms=max_wait_ms, policy=policy,
                         can_mix=can_mix, top_budget=top_budget,
                         slo_aware=slo_aware)
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if max_leg is not None and max_leg < 1:
            raise ValueError("max_leg must be >= 1")
        if not 0.0 < join_cost_cap <= 1.0:
            raise ValueError("join_cost_cap must be in (0, 1]")
        self.max_slots = max_slots
        self.boundaries = tuple(sorted(boundaries))
        self.max_leg = max_leg
        self.join_cost_cap = join_cost_cap
        self._join_buckets = self._bucket_sizes(max_slots)

    def join_bucket(self, count: int) -> int:
        """Smallest padded size for a join-prefix dispatch — powers of two
        up to ``max_slots``, so each (boundary, bucket) prefix program is
        compiled exactly once (mirrors ``bucket`` for flush batches)."""
        for b in self._join_buckets:
            if b >= count:
                return b
        raise ValueError(f"count {count} exceeds max_slots {self.max_slots}")

    def next_boundary(self, step: int) -> Optional[int]:
        """The next stop strictly beyond ``step`` (None past the top): the
        first exit boundary, clipped to ``max_leg`` steps when set — a
        shorter leg is not a join point, but it hands control back to the
        host so interleaved flushes are not blocked behind a long leg."""
        for b in self.boundaries:
            if b > step:
                return min(b, step + self.max_leg) if self.max_leg else b
        return None

    def plan_start(self, pending: Sequence[_Entry], now: float,
                   force: bool = False) -> list[_Entry]:
        """The FIFO slate opening a new trajectory: same-shape entries, up
        to ``max_slots``, once the slots would fill or the oldest entry of
        that shape has aged out (the same full-or-aged rule
        ``BatchScheduler.plan`` applies to flushes).

        Shape groups are considered INDEPENDENTLY, oldest group first —
        gating the slate on the overall-oldest entry's shape let one unaged
        singleton park a full (or aged) slate of another shape forever
        (head-of-line blocking across shapes). Mixed-shape traffic now
        starts whichever shape group is ready; the passed-over group stays
        pending and opens the next trajectory.

        SLO mode additionally starts as soon as any URGENT entry (deadline
        or raised priority) is queued: ``plan_start`` only runs when no
        trajectory is in flight — the device is idle — and unlike a flush,
        an under-filled trajectory costs nothing extra (its free slots
        refill at every exit boundary), so holding urgent work for the
        full-or-aged rule would burn deadline budget for no batching win."""
        groups: dict[tuple, list[_Entry]] = {}
        order = urgency_key if self.slo_aware else (lambda e: e.uid)
        for e in sorted(pending, key=order):
            groups.setdefault(e.shape_key, []).append(e)
        for same in groups.values():     # insertion order = oldest-first
            aged = any(now - e.t_submit >= self.max_wait_s for e in same)
            if self.slo_aware and not aged:
                aged = any(is_urgent(e) for e in same)
            if force or aged or len(same) >= self.max_slots:
                return same[:self.max_slots]
        return []

    @staticmethod
    def join_cost(e: _Entry, boundary: int) -> int:
        """Prefix forwards admitting ``e`` at ``boundary`` costs: a fresh
        join recomputes 0..boundary; a PREEMPTED entry paused at step s <=
        boundary resumes its saved carry and only pays s..boundary."""
        p = getattr(e, "paused", None)
        if p is not None and p.step <= boundary:
            return boundary - p.step
        return boundary

    def plan_joins(self, pending: Sequence[_Entry], boundary: int,
                   free_slots: int, shape_key: tuple) -> list[_Entry]:
        """Entries admitted into the in-flight trajectory at ``boundary``:
        FIFO entries (urgency-ordered in SLO mode) of the trajectory's
        shape whose served budget lies STRICTLY beyond the boundary (their
        exit is still ahead on the shared grid) and whose prefix is worth
        paying — the join costs ``join_cost`` prefix forwards, so
        admission requires ``cost <= join_cost_cap * served`` (default:
        the prefix may be at most half the budget; very late joins burn
        forwards a future flush would amortize better; a resumed
        preempted entry's cost is only the saved-step gap). Capped by the
        freed slots; not age-gated — immediate admission is the latency
        win."""
        if free_slots <= 0:
            return []
        order = urgency_key if self.slo_aware else (lambda e: e.uid)
        ok = [e for e in sorted(pending, key=order)
              if e.shape_key == shape_key and e.served > boundary
              and self.join_cost(e, boundary)
              <= self.join_cost_cap * e.served]
        return ok[:free_slots]

    def plan_preemptions(self, pending: Sequence[_Entry], boundary: int,
                         active: Sequence[tuple], free_slots: int,
                         shape_key: tuple) -> list[tuple]:
        """Eviction pairs ``(slot_idx, victim, urgent)`` at an exit
        boundary: each still-queued urgent entry that could join (same
        conditions as ``plan_joins``) displaces one STRICTLY-lower-
        priority occupied slot — lowest-priority, youngest victim first.
        Empty when free slots remain (``plan_joins`` already used them) or
        nothing queued outranks a resident. Pure planning; eviction is
        free by construction at an exit boundary (the victim resumes via
        its saved carry, bit-identical — ``core.anytime``'s join
        invariant)."""
        if free_slots > 0 or not pending or not active:
            return []
        candidates = [e for e in sorted(pending, key=urgency_key)
                      if e.shape_key == shape_key and e.served > boundary
                      and self.join_cost(e, boundary)
                      <= self.join_cost_cap * e.served]
        victims = sorted(
            [(si, v) for si, v in active if v.served > boundary],
            key=lambda sv: (sv[1].priority, -sv[1].t_submit, -sv[1].uid))
        pairs = []
        for e in candidates:
            if not victims:
                break
            si, v = victims[0]
            if v.priority >= e.priority:
                break       # victims are sorted; nothing weaker remains
            victims.pop(0)
            pairs.append((si, v, e))
        return pairs


@dataclasses.dataclass
class _Trajectory:
    """One in-flight shared trajectory: the device carry plus per-slot host
    bookkeeping. ``entries[i] is None`` marks a free (padded) slot — its
    rows keep stale data, which is harmless because rows are independent
    through the backbone (the padded-batch contract)."""

    carry: object                     # sampler-level AnytimeCarry
    entries: list                     # Optional[_Entry] per slot
    shape_key: tuple
    tokens: Optional[np.ndarray]      # (slots, S) conditioning, or None

    def cond(self) -> Optional[dict]:
        if self.tokens is None:
            return None
        return {"tokens": jnp.asarray(self.tokens)}

    def active(self) -> list[tuple[int, _Entry]]:
        return [(i, e) for i, e in enumerate(self.entries) if e is not None]

    def free_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.entries) if e is None]


class ContinuousGateway(Gateway):
    """Gateway with continuous batching over one anytime sampler.

    Same intake/lifecycle as ``Gateway``; ``pump`` becomes one engine tick:

    * no trajectory in flight — open one from the pending queue
      (``plan_start``), or
    * advance the trajectory one leg to the next exit boundary, release the
      slots exiting there, admit joiners into the freed slots
      (``plan_joins`` + prefix dispatch), and then
    * run the inherited flush planner over whatever remains pending, so
      non-joinable requests (budget at or below the next boundary, no free
      slot, other sample shape) never wait on the trajectory.

    ``drain`` additionally runs the in-flight trajectory to completion.
    """

    def __init__(self, sampler, *, max_slots: int = 8,
                 max_batch: Optional[int] = None, max_wait_ms: float = 10.0,
                 mixed_budget_policy: str = "auto", strict_nfe: bool = False,
                 mesh=None, clock=None, key=None,
                 max_leg: Optional[int] = None, join_cost_cap: float = 0.5,
                 metrics=None, recorder=None, slo=None, tiers=None):
        for method in ("carry_start", "carry_extend", "carry_warm"):
            if not hasattr(sampler, method):
                raise TypeError(
                    "continuous batching needs a resumable anytime sampler "
                    f"(missing {method!r}); use AnytimeFlowSampler or serve "
                    "through the flush-only Gateway")
        kw = {} if clock is None else {"clock": clock}
        super().__init__(sampler, max_batch=max_batch or max_slots,
                         max_wait_ms=max_wait_ms,
                         mixed_budget_policy=mixed_budget_policy,
                         strict_nfe=strict_nfe, mesh=mesh, key=key,
                         metrics=metrics, recorder=recorder, slo=slo,
                         tiers=tiers, **kw)
        self.scheduler = ContinuousScheduler(
            max_slots=max_slots, boundaries=sampler.budgets,
            max_batch=max_batch or max_slots, max_wait_ms=max_wait_ms,
            policy=mixed_budget_policy,
            can_mix=self.scheduler.can_mix,
            top_budget=max(sampler.budgets),
            max_leg=max_leg, join_cost_cap=join_cost_cap,
            slo_aware=slo is not None)
        self._traj: Optional[_Trajectory] = None
        self._place_carry = None
        # the narrowest leg: an eighth of the slots, three rungs of the
        # ladder below them (each narrower rung is one more program per
        # leg for the first trajectory of a shape to warm, and a step over
        # one row reads the same weights as over two), and the mesh's
        # data-axis size
        self._min_width = max(1, max_slots // 8)
        if mesh is not None:
            from repro.serving import sharded

            self._place_carry = sharded.carry_placer(mesh)
            self._min_width = max(self._min_width,
                                  sharded.data_axis_size(mesh))
        self._warmed: set = set()   # shape keys whose narrow legs are warm

    # -- engine tick ---------------------------------------------------------

    def pump(self, force: bool = False) -> int:
        """One engine tick; returns how many dispatches ran (trajectory
        opens and legs count as one each, like flush batches)."""
        ran = 0
        with self._plan_lock:
            if self.slo is not None:
                with profile_span("continuous.plan"):
                    self._shed_expired()
                    self.scheduler.lead_ms = self._dispatch_cost_ms()
            if self._traj is not None:
                try:
                    self._advance_leg()
                except BaseException as exc:  # noqa: BLE001 — see below
                    # a failing leg must not strand the slots' futures or
                    # kill the serve thread (the trajectory twin of the
                    # flush-path guard in Gateway._run_batches)
                    self._fail_trajectory(exc)
                ran += 1
            if self._traj is None:
                # idle engine, or the trajectory just retired: a new slate
                # gets first claim on the pending queue — a trajectory costs
                # what a mixed flush costs but its slots refill at every
                # later boundary, so it must outrank the flush planner
                with profile_span("continuous.plan"):
                    starters = self.scheduler.plan_start(
                        self.queue.snapshot(), self.clock(), force=force)
                    if starters:
                        self._take(starters)
                if starters:
                    try:
                        self._start_trajectory(starters, self.clock())
                    except BaseException as exc:  # noqa: BLE001
                        self._fail_entries(starters, exc, count_all=True)
                        self._settle(len(starters))
                        self._traj = None
                    ran += 1
            # interleave flushes: whatever neither joined nor started still
            # obeys the flush-only rules (full buckets now, partials aged)
            with profile_span("continuous.plan"):
                batches = self.scheduler.plan(
                    self.queue.snapshot(), self.clock(), force=force)
                self._take([e for b in batches for e in b.entries])
        return ran + self._run_batches(batches)

    def _estimate_wait_ms(self, entry) -> float:
        """Continuous-tier admission cost model: slots refill at every
        exit boundary, so the per-settled-request service time sits far
        below one whole dispatch (the flush model's unit) — joiners ride
        legs already paid for. The queue therefore drains at the OBSERVED
        device-time-per-settle rate, which the registry already tracks
        exactly (``device_dispatch_ms.sum`` — the enqueues — plus
        ``device_wait_ms.sum`` — the waits on results — over
        ``completed``). Before the first settle there is nothing to
        observe and the inherited flush batch model — seeded by
        ``slo.default_cost_ms`` — stands in."""
        with self._stats_lock:
            completed = self._m.completed.value
            device_ms = (self._m.device_dispatch_ms.sum
                         + self._m.device_wait_ms.sum)
            inflight = self._inflight
        if completed and device_ms > 0.0:
            # work ahead of us = queued entries plus the trajectory rows
            # already off the queue but not yet settled
            ahead = self.queue.depth() + inflight
            return device_ms / completed * (ahead + 1)
        return super()._estimate_wait_ms(entry)

    def _leg_width(self, live: int) -> int:
        """Rows a leg dispatches for ``live`` occupied slots: the join
        ladder's power of two, floored at ``_min_width``, capped at
        ``max_slots``."""
        slots = self.scheduler.max_slots
        return self.scheduler.join_bucket(
            min(max(live, self._min_width), slots))

    def _warm_legs(self, traj: _Trajectory) -> None:
        """Run once every narrow (leg, width) program a trajectory of this
        shape can dispatch — the legs from step 0 along ``next_boundary``,
        each width below ``max_slots`` — on the fresh carry, so no
        compilation waits for traffic. Full-width legs are not narrowed
        and warm as before."""
        slots = self.scheduler.max_slots
        widths = sorted({self._leg_width(n) for n in range(1, slots)}
                        - {slots})
        if not widths:
            return
        legs, step = [], 0
        while (stop := self.scheduler.next_boundary(step)) is not None:
            legs.append((step, stop))
            step = stop
        with profile_span("continuous.warm", programs=len(legs) * len(widths)):
            cond = traj.cond()
            for start, stop in legs:
                carry = traj.carry._replace(step=start)
                for w in widths:
                    self.sampler.carry_warm(cond, carry._replace(
                        rows=jnp.asarray(np.arange(w, dtype=np.int32))), stop)

    def _start_trajectory(self, starters: list, now: float) -> None:
        """Open a trajectory over ``starters`` (costs no forwards — the
        first leg runs on the next tick; waits end here, at admission)."""
        slots = self.scheduler.max_slots
        pad = slots - len(starters)
        with profile_span("continuous.assemble", rows=len(starters),
                          bucket=slots):
            t0 = self.clock()
            x0_np, tokens = assemble_rows(starters, slots)
            traj = _Trajectory(carry=None,
                               entries=list(starters) + [None] * pad,
                               shape_key=starters[0].shape_key, tokens=tokens)
            carry = self.sampler.carry_start(traj.cond(), jnp.asarray(x0_np))
            if self._place_carry is not None:
                carry = self._place_carry(carry)
            assembly_ms = (self.clock() - t0) * 1e3
        for e in starters:
            e.t_admit, e.join_step = now, 0
        traj.carry = carry
        self._traj = traj
        if traj.shape_key not in self._warmed:
            self._warm_legs(traj)     # a warm that raises is tried again
            self._warmed.add(traj.shape_key)
        with self._stats_lock:
            self._m.trajectories.inc()
            self._m.host_assembly_ms.observe(assembly_ms)
            self._note_program(f"start/k{slots}")
        rec = self.recorder
        if rec:
            for e in starters:
                rec.event(e.uid, "dispatch", now, host=self._host,
                          kind="traj_start")

    def _advance_leg(self) -> None:
        """Advance to the next exit boundary, release exiting slots, admit
        joiners into the freed slots."""
        traj = self._traj
        step = traj.carry.step
        boundary = self.scheduler.next_boundary(step)
        assert boundary is not None, "trajectory ran past the top budget"
        active = traj.active()
        width = self._leg_width(len(active))
        # exit row j is slot rows[j]: the live slots and, as padding, free
        # ones (their rows are stale; a join overwrites them whole); at
        # full width, every slot in order, through the unnarrowed program
        rows = sorted([si for si, _ in active]
                      + traj.free_slots()[:width - len(active)])
        carry = traj.carry
        if width < self.scheduler.max_slots:
            carry = carry._replace(rows=jnp.asarray(np.asarray(rows,
                                                               np.int32)))
        with profile_span(f"continuous.leg.{step}-{boundary}",
                          live=len(active), bucket=width):
            t0 = self.clock()   # gateway clock: fake-clock tests feed the
            #                     SLO cost model simulated dispatch times
            carry, exits = self.sampler.carry_extend(traj.cond(), carry,
                                                     boundary)
            if self._place_carry is not None:
                carry = self._place_carry(carry)
            leg_ms = (self.clock() - t0) * 1e3
        traj.carry = carry
        pos = {si: j for j, si in enumerate(rows)}
        # a max_leg-clipped stop is a control point, not an exit boundary:
        # nothing releases or joins there, but interleaved flushes can run
        is_exit = boundary in self.scheduler.boundaries
        released = [(si, e) for si, e in active
                    if is_exit and e.served == boundary]
        # streaming slots riding PAST this exit get the boundary's early-
        # exit latents as a partial (exactly the budget-`boundary` sample
        # for their noise — the anytime grid is nested)
        streaming = [(si, e) for si, e in active
                     if is_exit and e.sink is not None
                     and e.served > boundary]
        latents = wait_ms = None
        if released or streaming:
            with profile_span(f"continuous.sync.{step}-{boundary}"):
                t1 = self.clock()
                latents = np.asarray(exits[boundary])
                wait_ms = (self.clock() - t1) * 1e3
        with self._stats_lock:
            m = self._m
            m.legs.inc()
            m.forwards.inc(boundary - step)
            m.slot_steps_active.inc(len(active) * (boundary - step))
            m.slot_steps_total.inc(width * (boundary - step))
            m.device_dispatch_ms.observe(leg_ms)
            if wait_ms is not None:
                m.device_wait_ms.observe(wait_ms)
            self._note_rows(len(active), boundary - step)
            self._note_program(f"leg/{step}-{boundary}-k{width}")
            if active and active[0][1].native_shape is not None:
                # per-tier occupancy, weighted by leg steps (the slot-
                # steps convention): native rows carried vs padded rows
                # paid for — slot padding AND tier padding in one ratio
                tier = traj.shape_key[1]
                steps = boundary - step
                self._note_tier(
                    tier,
                    steps * sum(e.native_shape[0] for _, e in active),
                    steps * width * tier[0])
        if latents is not None:
            with profile_span(f"continuous.release.{boundary}",
                              rows=len(released)):
                for si, e in streaming:
                    e.sink.partial(crop_row(latents[pos[si]], e.native_shape),
                                   boundary=boundary)
                for si, e in released:
                    self._release(traj, si, e,
                                  crop_row(latents[pos[si]], e.native_shape),
                                  boundary, len(active), width)
        if is_exit:
            with profile_span("continuous.plan"):
                joiners = self.scheduler.plan_joins(
                    self.queue.snapshot(), boundary, len(traj.free_slots()),
                    traj.shape_key)
                if joiners:
                    self._take(joiners)
            if joiners:
                try:
                    self._admit(traj, joiners, boundary)
                except BaseException as exc:  # noqa: BLE001
                    # joiners left the queue already; a failing prefix
                    # dispatch must reach their futures. The trajectory's
                    # own carry is untouched (assigned only after every
                    # scatter lands), so the in-flight slots roll on.
                    self._fail_entries(joiners, exc, count_all=True)
                    self._settle(len(joiners))
            if self.slo is not None and self.slo.preemption:
                self._preempt(traj, boundary)
        if not traj.active():
            self._traj = None

    def _release(self, traj: _Trajectory, si: int, e: _Entry, row,
                 boundary: int, batch_real: int, batch_padded: int) -> None:
        """Resolve one slot's future at its exit boundary and free the slot."""
        wait_ms = (e.t_admit - e.t_submit) * 1e3
        with self._stats_lock:
            # wait observed exactly where completed ticks, so the
            # histogram count == completed invariant holds tier-wide
            self._m.completed.inc()
            self._m.wait_ms.observe(wait_ms)
            self._note_deadline(e, self.clock())
            self._inflight -= 1      # taken at plan_start/plan_joins
        rec = self.recorder
        if rec:
            rec.event(e.uid, "settle", self.clock(), host=self._host,
                      status="completed", boundary=boundary, slot=si)
        response = Response(latents=row, meta={
            "requested_budget": e.requested,
            "served_budget": e.served,
            "nfe_batch": boundary,
            "batch_real": batch_real,
            "batch_padded": batch_padded,
            "mixed": False,
            "wait_ms": wait_ms,
            "continuous": True,
            "join_step": e.join_step,
            "slot": si,
        })
        if e.native_shape is not None:
            response.meta["tier_shape"] = e.shape_key[1]
            response.meta["native_shape"] = e.native_shape
        if e.trace and rec:
            response.trace = rec.trace(e.uid)
        try:
            e.future.set_result(response)
        except Exception:           # cancelled: the trajectory rolls on
            pass
        if e.sink is not None:
            e.sink.final(response)
        traj.entries[si] = None

    def _admit(self, traj: _Trajectory, joiners: list, boundary: int) -> None:
        """Join ``joiners`` at ``boundary``. Fresh joiners compute their
        prefix 0..boundary from their own noise on the shared intermediate
        coefficients (one padded mini-dispatch, ``boundary`` forwards);
        PREEMPTED joiners resume their saved carry from its paused step
        (``boundary - step`` forwards, zero when paused at this very
        boundary). Both land by scattering per-slot carry columns into the
        freed slots — bit-identical to never having left the trajectory
        (the exit-boundary join invariant) — then re-place on the mesh if
        sharded."""
        fresh = [e for e in joiners
                 if e.paused is None or e.paused.step > boundary]
        resumed = [e for e in joiners
                   if e.paused is not None and e.paused.step <= boundary]
        carriers: list[tuple] = []    # (entries, carry holding their rows)
        programs: list[str] = []
        assembly_ms: list[float] = []
        rows_steps: list[tuple] = []  # (real rows, NFE steps) per dispatch
        if fresh:
            k = len(fresh)
            bucket = self.scheduler.join_bucket(k)
            with profile_span("continuous.assemble", rows=k, bucket=bucket):
                t0 = self.clock()
                x0_np, t_np = assemble_rows(fresh, bucket)
                cond = None if t_np is None else {"tokens": jnp.asarray(t_np)}
                prefix = self.sampler.carry_start(cond, jnp.asarray(x0_np))
                assembly_ms.append((self.clock() - t0) * 1e3)
            with profile_span(f"continuous.join.{boundary}/k{bucket}",
                              rows=k):
                prefix, _ = self.sampler.carry_extend(cond, prefix, boundary)
            rows_steps.append((k, boundary))
            programs.append(f"join/{boundary}-k{bucket}")
            carriers.append((fresh, prefix))
        by_step: dict[int, list] = {}
        for e in resumed:
            by_step.setdefault(e.paused.step, []).append(e)
        for s in sorted(by_step):
            group = by_step[s]
            k = len(group)
            bucket = self.scheduler.join_bucket(k)
            with profile_span("continuous.assemble", rows=k, bucket=bucket):
                t0 = self.clock()
                x0_np, u_np, x_np, t_np = self._stack_paused(group, bucket)
                rcarry = type(traj.carry)(
                    x0=jnp.asarray(x0_np), U=jnp.asarray(u_np),
                    x=jnp.asarray(x_np), step=s)
                cond = (None if t_np is None or s == boundary
                        else {"tokens": jnp.asarray(t_np)})
                assembly_ms.append((self.clock() - t0) * 1e3)
            if s < boundary:
                with profile_span(
                        f"continuous.resume.{s}-{boundary}/k{bucket}",
                        rows=k):
                    rcarry, _ = self.sampler.carry_extend(cond, rcarry,
                                                          boundary)
                rows_steps.append((k, boundary - s))
                programs.append(f"resume/{s}-{boundary}-k{bucket}")
            carriers.append((group, rcarry))
        free = traj.free_slots()[:len(joiners)]
        with profile_span(f"continuous.scatter.{boundary}",
                          rows=len(joiners)):
            cols: dict[int, tuple] = {}   # uid -> (x0 row, U column, x row)
            for group, c in carriers:
                for i, e in enumerate(group):
                    cols[e.uid] = (c.x0[i], c.U[:, i], c.x[i])
            idx = jnp.asarray(free)
            carry = traj.carry
            carry = carry._replace(
                x0=carry.x0.at[idx].set(
                    jnp.stack([cols[e.uid][0] for e in joiners])),
                U=carry.U.at[:, idx].set(
                    jnp.stack([cols[e.uid][1] for e in joiners], axis=1)),
                x=carry.x.at[idx].set(
                    jnp.stack([cols[e.uid][2] for e in joiners])))
            if self._place_carry is not None:
                carry = self._place_carry(carry)
        traj.carry = carry
        now = self.clock()
        rec = self.recorder
        for si, e in zip(free, joiners):
            if e.paused is None:
                # a resumed entry keeps its FIRST admission: its wait
                # ended then, and join_step records where it entered
                e.t_admit, e.join_step = now, boundary
            e.paused = None
            if traj.tokens is not None:
                traj.tokens[si] = np.asarray(e.tokens)
            traj.entries[si] = e
            if rec:
                rec.event(e.uid, "join", now, host=self._host,
                          boundary=boundary, slot=si,
                          resumed=e in resumed)
        prefix_forwards = sum(steps for _, steps in rows_steps)
        with self._stats_lock:
            m = self._m
            m.joins.inc(len(joiners))
            m.forwards.inc(prefix_forwards)
            m.join_forwards.inc(prefix_forwards)
            for ms in assembly_ms:
                m.host_assembly_ms.observe(ms)
            for rows, steps in rows_steps:
                self._note_rows(rows, steps)
            for program in programs:
                self._note_program(program)

    @staticmethod
    def _stack_paused(group: list, bucket: int):
        """Rebuild padded batch arrays from saved ``PausedCarry`` columns
        (the resume twin of ``assemble_rows``): stack each victim's x0
        row, recorded-velocity column, and state row, zero-padded to
        ``bucket`` — pad rows are independent through the backbone, so
        they never perturb a resumed sample."""
        pad = bucket - len(group)
        x0 = np.stack([np.asarray(e.paused.x0) for e in group])
        u = np.stack([np.asarray(e.paused.U) for e in group], axis=1)
        x = np.stack([np.asarray(e.paused.x) for e in group])
        if pad:
            x0 = np.concatenate(
                [x0, np.zeros((pad,) + x0.shape[1:], x0.dtype)])
            u = np.concatenate(
                [u, np.zeros((u.shape[0], pad) + u.shape[2:], u.dtype)],
                axis=1)
            x = np.concatenate(
                [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        tokens = None
        if group[0].tokens is not None:
            tokens = np.stack(
                [np.asarray(e.tokens) for e in group]
                + [np.zeros_like(np.asarray(group[0].tokens))] * pad)
        return x0, u, x, tokens

    def _preempt(self, traj: _Trajectory, boundary: int) -> None:
        """Evict strictly-lower-priority slots for queued urgent entries at
        an exit boundary (``plan_preemptions``), then admit the urgent
        entries into the freed slots. Each victim's carry column is
        snapshotted to host (``PausedCarry``) and the victim goes BACK to
        the queue; a later ``plan_joins`` resumes it for only the
        boundary-gap forwards, bit-identical to an unpreempted run."""
        with profile_span("continuous.plan"):
            pairs = self.scheduler.plan_preemptions(
                self.queue.snapshot(), boundary, traj.active(),
                len(traj.free_slots()), traj.shape_key)
        if not pairs:
            return
        carry = traj.carry
        rec = self.recorder
        with profile_span(f"continuous.sync.preempt.{boundary}"):
            t0 = self.clock()
            snaps = [PausedCarry(step=boundary,
                                 x0=np.asarray(carry.x0[si]),
                                 U=np.asarray(carry.U[:, si]),
                                 x=np.asarray(carry.x[si]))
                     for si, _, _ in pairs]
            wait_ms = (self.clock() - t0) * 1e3
        now = self.clock()
        urgents = []
        for (si, victim, urgent), snap in zip(pairs, snaps):
            victim.paused = snap
            traj.entries[si] = None
            # back to the queue: still accepted (submitted already
            # counted), no longer in flight until it rejoins
            self.queue.push(victim)
            self._settle(1)
            urgents.append(urgent)
            if rec:
                rec.event(victim.uid, "preempt", now, host=self._host,
                          boundary=boundary, slot=si, by=urgent.uid)
        with self._stats_lock:
            self._m.preemptions.inc(len(pairs))
            self._m.device_wait_ms.observe(wait_ms)
        self._take(urgents)
        try:
            self._admit(traj, urgents, boundary)
        except BaseException as exc:  # noqa: BLE001 — mirror plan_joins
            self._fail_entries(urgents, exc, count_all=True)
            self._settle(len(urgents))

    def _fail_trajectory(self, exc: BaseException) -> None:
        """Surface a failing leg into every occupied slot's future and
        retire the trajectory, keeping the engine (and its serve thread)
        alive — the trajectory twin of ``_run_batches``' per-batch guard."""
        traj, self._traj = self._traj, None
        if traj is not None:
            entries = [e for _, e in traj.active()]
            self._fail_entries(entries, exc, count_all=True)
            self._settle(len(entries))

    # -- lifecycle -----------------------------------------------------------

    def _drained(self) -> bool:
        """Drain additionally runs the in-flight trajectory to completion
        (its slots are in flight anyway — belt and braces)."""
        return super()._drained() and self._traj is None
