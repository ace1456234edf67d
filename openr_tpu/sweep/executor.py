"""Sharded, resumable scenario-sweep executor.

Scenarios (hash-sorted within their world, worlds contiguous) pack into
fixed-size shards; each shard is dispatched as COMMITTED per-device
work on one healthy DevicePool chip (round-robin over the survivors at
dispatch time), solved through the warm-start repair sweep
(:class:`~openr_tpu.ops.whatif.LinkFailureSweep` +
:class:`~openr_tpu.ops.sweep_select.SweepRouteSelector` — the headline
what-if machinery) for single-area LSDBs, or through the multi-area
what-if kernel (:func:`~openr_tpu.ops.fleet_tables
.whatif_multi_area_tables`) for multi-area ones.  Up to ``inflight``
shards ride the streamed drain path at once (dispatch shard N+1 while
shard N's delta compaction is still on device; drains commit in FIFO
order so the spill layout is deterministic).

Resilience/resume contract:

* a shard whose dispatch or drain raises quarantines ITS chip through
  the governor (``record_stream_failure`` — the PR-11 streamed-failure
  path) and re-packs ONLY that shard onto the next survivor; committed
  shards are never re-run;
* after every committed shard the spill is durable and the checkpoint
  manifest records it, so a killed sweep resumes from the last
  committed shard: the resume replays committed rows from the spill
  into a fresh reducer (verifying counts against the manifest) and
  continues with the first uncommitted shard;
* planning rides the content-hash ``build_repair_plan_cached`` cache:
  a prefix-churn generation bump mid-sweep rebuilds the candidate
  tables but every world's repair plan is a cache hit (the topology
  content is unchanged), so the sweep never restarts planning.

Phase attribution: shard solves record under the
``pipeline.sweep_shard_solve`` phase (device-attributed, per-chip busy
time on the shared ledger), drains under ``pipeline.stream_drain``, row
decode under ``pipeline.decode``, and the reducer + spill under
``pipeline.sweep_reduce`` — the bench proves the sweep is device-bound
from exactly these histograms.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from openr_tpu.sweep.reduce import SweepReducer, replay_reducer
from openr_tpu.sweep.scenario import (
    Scenario,
    ScenarioSpec,
    World,
    enumerate_scenarios,
    metric_matcher,
    scenario_set_hash,
)
from openr_tpu.sweep.spill import CheckpointManifest, SpillReader, SpillWriter


class SweepError(RuntimeError):
    """Sweep cannot start/continue (no LSDB, drained vantage, no
    surviving devices, spill/checkpoint disagreement on resume)."""


@dataclasses.dataclass
class SweepInputs:
    """Everything the executor reads from the decision plane.  Pulled
    fresh via ``inputs_fn`` before every context (re)build, so a
    generation bump mid-sweep is picked up at the next shard."""

    area_link_states: dict
    prefix_state: object
    change_seq: int
    root: str
    pool: object = None
    probe: object = None
    governor: object = None
    per_area_distance: bool = False


class _ShardHandle:
    """One in-flight shard: its dispatched world groups + bookkeeping."""

    __slots__ = ("shard_id", "groups", "device_index", "t0")

    def __init__(self, shard_id, groups, device_index, t0):
        self.shard_id = shard_id
        self.groups = groups
        self.device_index = device_index
        self.t0 = t0


class SweepExecutor:
    def __init__(
        self,
        inputs_fn: Callable[[], SweepInputs],
        spill_dir: str,
        clock=None,
        counters=None,
        shard_scenarios: int = 1024,
        segment_rows: int = 8192,
        top_k: int = 64,
        inflight: int = 2,
        engine_cache_entries: int = 8,
    ) -> None:
        from openr_tpu.common.runtime import CounterMap
        from openr_tpu.tracing.pipeline import disabled_probe

        if shard_scenarios < 1:
            raise ValueError("shard_scenarios must be >= 1")
        self.inputs_fn = inputs_fn
        self.spill_dir = spill_dir
        self.clock = clock
        self.counters = counters if counters is not None else CounterMap()
        self.shard_scenarios = shard_scenarios
        self.segment_rows = segment_rows
        self.top_k = top_k
        self.inflight_limit = max(1, inflight)
        self._engine_cache_entries = max(1, engine_cache_entries)
        self._probe = disabled_probe()
        self.spec: Optional[ScenarioSpec] = None
        self.scenarios: List[Scenario] = []
        self.set_hash = ""
        self.sweep_id = ""
        self.shards: List[Tuple[int, int, int]] = []
        self.completed: set = set()
        self.resumed_shards = 0
        self.reducer = SweepReducer(top_k=top_k)
        self.spill: Optional[SpillWriter] = None
        self.checkpoint: Optional[CheckpointManifest] = None
        self.cancelled = False
        #: per-(ctx epoch, world, chip) engine cache, LRU-bounded
        self._engines: "collections.OrderedDict" = collections.OrderedDict()
        self._ctx = None
        self._ctx_key = None
        self._ctx_epoch = 0
        self._rr = 0  # device round-robin cursor
        self.num_device_solves = 0
        self.num_repacked_shards = 0
        self.generations_observed: set = set()
        #: optional rider on the drained single-area deltas
        #: (ctx, shard_id, group, deltas) — the protection tier's patch
        #: compaction consumes the SAME drained pass the reducer's row
        #: extraction reads (reduce.world_deltas), never a second sweep
        self.delta_consumer = None
        #: optional per-shard durability rider, called between the spill
        #: append and the checkpoint commit (same crash discipline)
        self.commit_hook = None

    # -- preparation / resume ----------------------------------------------

    def prepare(self, spec: ScenarioSpec, resume: bool = True) -> dict:
        """Enumerate, shard, and (when a matching checkpoint exists)
        resume: committed shards are skipped and their rows replayed
        from the spill into the reducer.  Returns the prepare report."""
        inputs = self.inputs_fn()
        if not inputs.area_link_states:
            raise SweepError("no LSDB yet — nothing to sweep")
        for s in spec.drain_node_sets:
            if inputs.root in s:
                raise SweepError(
                    f"drain set {list(s)} drains the sweep vantage "
                    f"{inputs.root!r}"
                )
        self.spec = spec
        pairs = self._all_pairs(inputs)
        self.scenarios = enumerate_scenarios(spec, pairs)
        if not self.scenarios:
            raise SweepError("the grammar enumerates zero scenarios")
        self.set_hash = scenario_set_hash(spec, self.scenarios)
        self.sweep_id = self.set_hash[:16]
        self.shards = []
        for i, lo in enumerate(
            range(0, len(self.scenarios), self.shard_scenarios)
        ):
            self.shards.append(
                (i, lo, min(lo + self.shard_scenarios, len(self.scenarios)))
            )
        self.checkpoint = CheckpointManifest(self.spill_dir)
        if not (resume and self.checkpoint.matches(self.set_hash)):
            # fresh sweep: a clean spill.  Stale segments from an
            # earlier sweep in the same directory would otherwise be
            # appended to — and a LATER resume's shard-id replay could
            # collide with the old sweep's identically-numbered shards
            self._wipe_spill()
        self.spill = SpillWriter(
            self.spill_dir, segment_rows=self.segment_rows
        )
        self.completed = set()
        self.resumed_shards = 0
        self.reducer = SweepReducer(top_k=self.top_k)
        if resume and self.checkpoint.matches(self.set_hash):
            committed = self.checkpoint.completed_shards()
            self.completed = set(committed)
            self.resumed_shards = len(self.completed)
            if self.completed:
                self.reducer = replay_reducer(
                    SpillReader(self.spill_dir),
                    self.completed,
                    top_k=self.top_k,
                )
                expect = sum(m["rows"] for m in committed.values())
                if self.reducer.scenarios != expect:
                    raise SweepError(
                        f"spill/checkpoint disagree on resume: manifest "
                        f"says {expect} committed rows, spill replayed "
                        f"{self.reducer.scenarios}"
                    )
                self.counters.bump("sweep.resumes")
                self.counters.bump(
                    "sweep.resumed_shards", self.resumed_shards
                )
        else:
            self.checkpoint.reset(
                self.sweep_id,
                self.set_hash,
                spec.content(),
                len(self.scenarios),
            )
        return {
            "sweep_id": self.sweep_id,
            "set_hash": self.set_hash,
            "scenarios": len(self.scenarios),
            "shards": len(self.shards),
            "resumed_shards": self.resumed_shards,
        }

    def _wipe_spill(self) -> None:
        """Drop every spill segment + the index (fresh-sweep reset;
        the checkpoint itself is replaced by ``reset``)."""
        import os

        from openr_tpu.sweep.spill import INDEX_NAME

        try:
            names = os.listdir(self.spill_dir)
        except OSError:
            return
        for name in names:
            if name == INDEX_NAME or (
                name.startswith("rows-") and name.endswith(".jsonl")
            ):
                try:
                    os.unlink(os.path.join(self.spill_dir, name))
                except OSError:
                    pass

    @staticmethod
    def _all_pairs(inputs: SweepInputs) -> List[Tuple[str, str]]:
        pairs = set()
        for _area, ls in sorted(inputs.area_link_states.items()):
            for link in ls.all_links():
                pairs.add(tuple(sorted((link.n1, link.n2))))
        return sorted(pairs)

    def pending_shards(self) -> List[int]:
        return [s[0] for s in self.shards if s[0] not in self.completed]

    # -- context -----------------------------------------------------------

    def _context(self):
        """(Re)build the shared solve context when the generation moved.
        Keyed exactly like the what-if engines: (change_seq, per-area
        topology seq).  A prefix-churn bump re-encodes candidates but
        every world's repair plan is a ``build_repair_plan_cached``
        content-hash hit — the 'planning never restarts' property."""
        inputs = self.inputs_fn()
        key = (
            inputs.change_seq,
            tuple(
                (a, inputs.area_link_states[a].topology_seq)
                for a in sorted(inputs.area_link_states)
            ),
        )
        self.generations_observed.add(key)
        if self._ctx is not None and self._ctx_key == key:
            return self._ctx
        from openr_tpu.tracing.pipeline import disabled_probe

        self._probe = (
            inputs.probe if inputs.probe is not None else disabled_probe()
        )
        multi = len(inputs.area_link_states) > 1
        if multi:
            ctx = self._build_multi_context(inputs)
        else:
            ctx = self._build_single_context(inputs)
        ctx["inputs"] = inputs
        ctx["multi"] = multi
        self._ctx = ctx
        self._ctx_key = key
        self._ctx_epoch += 1
        self.counters.bump("sweep.context_builds")
        return ctx

    def _build_single_context(self, inputs: SweepInputs) -> dict:
        from openr_tpu.decision.whatif_api import build_pair_links
        from openr_tpu.ops.csr import encode_link_state, encode_prefix_candidates
        from openr_tpu.tracing import pipeline

        (area, ls), = inputs.area_link_states.items()
        with self._probe.phase(pipeline.ENCODE):
            topo = encode_link_state(ls)
        if inputs.root not in topo.node_ids:
            raise SweepError(
                f"vantage {inputs.root!r} absent from the LSDB"
            )
        with self._probe.phase(pipeline.HOST_FETCH):
            cands = encode_prefix_candidates(
                inputs.prefix_state, topo, area
            )
        return {
            "topo": topo,
            "cands": cands,
            "pair_links": build_pair_links(topo.links),
            "root": inputs.root,
        }

    def _build_multi_context(self, inputs: SweepInputs) -> dict:
        from openr_tpu.decision.backend import DEGREE_BUCKETS
        from openr_tpu.decision.cand_table import CandidateTable
        from openr_tpu.decision.whatif_api import build_pair_links
        from openr_tpu.ops.csr import bucket_for, encode_multi_area
        from openr_tpu.tracing import pipeline

        with self._probe.phase(pipeline.ENCODE):
            enc = encode_multi_area(
                inputs.area_link_states, inputs.root
            )
        with self._probe.phase(pipeline.HOST_FETCH):
            table = CandidateTable()
            table.full_sync(inputs.prefix_state)
            dv = table.derived(enc)
            link_index = np.stack([t.link_index for t in enc.topos])
            pair_links: Dict = {}
            for ai, t in enumerate(enc.topos):
                for pair, vals in build_pair_links(
                    t.links, area_index=ai
                ).items():
                    pair_links.setdefault(pair, []).extend(vals)
        D = bucket_for(max(enc.max_out_degree(), 1), DEGREE_BUCKETS)
        return {
            "enc": enc,
            "table": table,
            "dv": dv,
            "link_index": link_index,
            "pair_links": pair_links,
            "D": D,
            "root": inputs.root,
        }

    # -- world transforms ---------------------------------------------------

    @staticmethod
    def _world_single_topo(topo, world: World):
        """The world's encoded topology: drained nodes out of transit,
        matched link metrics scaled — derived arrays only, layout
        shared.  The dense in-edge planes are dropped (they embed the
        unscaled weights); the repair-sweep kernels read the edge lists
        directly."""
        if not world.drained_nodes and world.metric is None:
            return topo
        w = topo.w
        overloaded = topo.overloaded
        if world.metric is not None:
            match = metric_matcher(world.metric[0])
            scale_link = np.zeros(max(len(topo.links), 1), bool)
            for li, link in enumerate(topo.links):
                if match(link.n1, link.n2):
                    scale_link[li] = True
            edge_scaled = (topo.link_index >= 0) & scale_link[
                np.clip(topo.link_index, 0, None)
            ]
            w = np.where(
                edge_scaled, topo.w * np.float32(world.metric[1]), topo.w
            ).astype(np.float32)
        if world.drained_nodes:
            overloaded = topo.overloaded.copy()
            for name in world.drained_nodes:
                slot = topo.node_ids.get(name)
                if slot is not None:
                    overloaded[slot] = True
        return dataclasses.replace(
            topo,
            w=w,
            overloaded=overloaded,
            in_src=None,
            in_w=None,
            in_ok=None,
            in_rank=None,
            in_edge_pos=None,
            in_has=None,
        )

    # -- engines -----------------------------------------------------------

    def _device_ctx(self, device_index: Optional[int], pool):
        import contextlib

        import jax

        from openr_tpu.ops import jit_guard

        stack = contextlib.ExitStack()
        if pool is not None and device_index is not None:
            stack.enter_context(
                jax.default_device(pool.device(device_index))
            )
            stack.enter_context(jit_guard.dispatch_device(device_index))
        return stack

    def _engine_for(self, ctx, world: World, device_index: Optional[int]):
        """(LinkFailureSweep, SweepRouteSelector) for one (context
        epoch, world, chip) — LRU-bounded; a rebuilt engine's plan()
        rides the content-hash plan cache, so re-creation after a
        prefix-churn context rebuild never replans."""
        key = (self._ctx_epoch, world.key(), device_index)
        hit = self._engines.get(key)
        if hit is not None:
            self._engines.move_to_end(key)
            return hit
        from openr_tpu.ops.sweep_select import SweepRouteSelector
        from openr_tpu.ops.whatif import LinkFailureSweep

        from openr_tpu.tracing import pipeline

        pool = ctx["inputs"].pool
        topo_w = self._world_single_topo(ctx["topo"], world)
        # engine construction is part of the solve budget (base solve +
        # the content-hash-memoized planner pass + selector tables)
        with self._device_ctx(device_index, pool), self._probe.phase(
            pipeline.SWEEP_SHARD_SOLVE, device=device_index
        ):
            sweep = LinkFailureSweep(topo_w, ctx["root"])
            sweep.plan()  # content-hash memoized planner pass
            selector = SweepRouteSelector(
                topo_w, ctx["root"], ctx["cands"], max_degree=sweep.D
            )
        self._engines[key] = (sweep, selector)
        while len(self._engines) > self._engine_cache_entries:
            self._engines.popitem(last=False)
        self.counters.bump("sweep.engine_builds")
        return self._engines[key]

    # -- dispatch / drain ---------------------------------------------------

    def _pick_device(self, pool, exclude=()) -> Optional[int]:
        if pool is None:
            return None
        healthy = [
            i for i in pool.healthy_indices() if i not in exclude
        ]
        if not healthy:
            raise SweepError("no surviving devices to dispatch on")
        dev = healthy[self._rr % len(healthy)]
        self._rr += 1
        return dev

    def _resolve_failures(self, ctx, scenario: Scenario):
        """Scenario link pairs -> the flat failed-link-id set (parallel
        bundles fail whole), or None for an unknown pair (topology
        drifted under the scenario set)."""
        ids: List = []
        for pair in scenario.failed_links:
            hits = ctx["pair_links"].get(frozenset(pair))
            if not hits:
                return None
            ids.extend(hits)
        return tuple(ids)

    def _dispatch_shard(
        self, shard_id: int, dev: Optional[int]
    ) -> _ShardHandle:
        from openr_tpu.tracing import pipeline

        ctx = self._context()
        _sid, lo, hi = self.shards[shard_id]
        scenarios = self.scenarios[lo:hi]
        pool = ctx["inputs"].pool
        groups = []
        # worlds are contiguous within a shard by enumeration order;
        # group defensively anyway
        by_world: "collections.OrderedDict" = collections.OrderedDict()
        for scen in scenarios:
            by_world.setdefault(scen.world.key(), []).append(scen)
        t0 = self.clock.now() if self.clock is not None else 0.0
        # sorted is an identity here (scenarios arrive (world key, hash)-
        # sorted, so insertion order == sorted order) but makes the
        # solve/spill order provably content-derived (orlint
        # unordered-emission)
        for _wkey, items in sorted(by_world.items()):
            world = items[0].world
            fail_sets = []
            errors = []
            for scen in items:
                ids = self._resolve_failures(ctx, scen)
                errors.append(ids is None)
                fail_sets.append(ids if ids is not None else ())
            if ctx["multi"]:
                stats = self._solve_multi(ctx, world, fail_sets, dev)
                groups.append(
                    {
                        "world": world,
                        "items": items,
                        "errors": errors,
                        "pending": None,
                        "stats": stats,
                    }
                )
                continue
            sweep, selector = self._engine_for(ctx, world, dev)
            with self._device_ctx(dev, pool), self._probe.phase(
                pipeline.SWEEP_SHARD_SOLVE, device=dev
            ):
                result = sweep.run_sets(fail_sets, fetch=False)
                pending = selector.start(result)
            if pool is not None and dev is not None:
                pool.note_inflight(dev)
            self.num_device_solves += result.num_device_solves
            self.counters.bump(
                "sweep.device_solves", result.num_device_solves
            )
            groups.append(
                {
                    "world": world,
                    "items": items,
                    "errors": errors,
                    "pending": pending,
                }
            )
        self.counters.bump("sweep.shards_dispatched")
        return _ShardHandle(shard_id, groups, dev, t0)

    def drain_ready(self, handle: _ShardHandle) -> bool:
        return all(
            g["pending"] is None or g["pending"].is_ready()
            for g in handle.groups
        )

    def _drain_shard(self, handle: _ShardHandle) -> List[dict]:
        from openr_tpu.tracing import pipeline

        rows: List[dict] = []
        pool = self._ctx["inputs"].pool if self._ctx else None
        single_groups = 0
        for g in handle.groups:
            if g["pending"] is not None:
                single_groups += 1
                with self._probe.phase(
                    pipeline.STREAM_DRAIN, device=handle.device_index
                ):
                    deltas = g["pending"].finish()
                if self.delta_consumer is not None:
                    self.delta_consumer(
                        self._ctx, handle.shard_id, g, deltas
                    )
                with self._probe.phase(pipeline.DECODE):
                    rows.extend(
                        self._rows_single(handle.shard_id, g, deltas)
                    )
            else:
                with self._probe.phase(pipeline.DECODE):
                    rows.extend(self._rows_multi(handle.shard_id, g))
        if single_groups and pool is not None and handle.device_index is not None:
            pool.note_complete(handle.device_index)
        if self.clock is not None:
            self.counters.observe(
                "sweep.shard_solve_ms",
                (self.clock.now() - handle.t0) * 1000.0,
            )
        return rows

    # -- row extraction -----------------------------------------------------

    def _rows_single(self, shard_id, group, deltas) -> List[dict]:
        from openr_tpu.sweep.reduce import world_deltas

        stats_of_row: Dict[int, tuple] = {}
        rows = []
        for scen, solve, r, delta in world_deltas(group, deltas):
            if solve == "error":
                rows.append(self._row(shard_id, scen, None, "error"))
                continue
            if solve == "alias":
                rows.append(
                    self._row(shard_id, scen, (0, 0, 0, 0.0), "alias")
                )
                continue
            stats = stats_of_row.get(r)
            if stats is None:
                p_idx, valid, metric, _lanes = delta
                was = deltas.base_valid[p_idx]
                withdrawn = int((~valid & was).sum())
                added = int((valid & ~was).sum())
                both = valid & was
                inc = 0.0
                if both.any():
                    diffs = metric[both] - deltas.base_metric[p_idx[both]]
                    if len(diffs):
                        inc = float(max(float(diffs.max()), 0.0))
                stats = (len(p_idx), withdrawn, added, round(inc, 3))
                stats_of_row[r] = stats
            rows.append(self._row(shard_id, scen, stats, "device"))
        return rows

    def _rows_multi(self, shard_id, group) -> List[dict]:
        rows = []
        stats = group["stats"]
        for k, (scen, is_err) in enumerate(
            zip(group["items"], group["errors"])
        ):
            if is_err:
                rows.append(self._row(shard_id, scen, None, "error"))
            else:
                rows.append(
                    self._row(shard_id, scen, stats[k], "device")
                )
        return rows

    @staticmethod
    def _row(shard_id, scen: Scenario, stats, solve: str) -> dict:
        changed, withdrawn, added, inc = stats or (0, 0, 0, 0.0)
        return {
            "shard": shard_id,
            "hash": scen.hash,
            "world": scen.world.key(),
            "failure": [list(p) for p in scen.failed_links],
            "domains": list(scen.domains),
            "changed": changed,
            "withdrawn": withdrawn,
            "added": added,
            "max_metric_increase": inc,
            "solve": solve,
        }

    # -- the multi-area solve ----------------------------------------------

    def _solve_multi(self, ctx, world: World, fail_sets, dev) -> List[tuple]:
        import jax
        import jax.numpy as jnp

        from openr_tpu.decision.whatif_api import FAILURE_BUCKETS
        from openr_tpu.ops.csr import bucket_for
        from openr_tpu.ops.fleet_tables import whatif_multi_area_tables
        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.tracing import pipeline

        enc, dv = ctx["enc"], ctx["dv"]
        pool = ctx["inputs"].pool
        B = len(fail_sets)
        bucket = bucket_for(
            B + 1, FAILURE_BUCKETS + (max(B + 1, FAILURE_BUCKETS[-1]),)
        )
        smax = max([len(t) for t in fail_sets] or [1]) or 1
        S = bucket_for(smax, (1, 2, 4, 8, 16, 32, max(smax, 32)))
        fa = np.full((bucket, S), -1, np.int32)
        fl = np.full((bucket, S), -1, np.int32)
        for i, tup in enumerate(fail_sets):
            for s, (ai, li) in enumerate(tup):
                fa[i, s], fl[i, s] = ai, li
        w = enc.w
        overloaded = enc.overloaded
        if world.metric is not None:
            match = metric_matcher(world.metric[0])
            w = enc.w.copy()
            for ai, t in enumerate(enc.topos):
                scale_link = np.zeros(max(len(t.links), 1), bool)
                for li, link in enumerate(t.links):
                    if match(link.n1, link.n2):
                        scale_link[li] = True
                edge_scaled = (t.link_index >= 0) & scale_link[
                    np.clip(t.link_index, 0, None)
                ]
                w[ai] = np.where(
                    edge_scaled,
                    enc.w[ai] * np.float32(world.metric[1]),
                    enc.w[ai],
                ).astype(np.float32)
        if world.drained_nodes:
            overloaded = enc.overloaded.copy()
            for ai, t in enumerate(enc.topos):
                for name in world.drained_nodes:
                    slot = t.node_ids.get(name)
                    if slot is not None:
                        overloaded[ai, slot] = True
        kernel_args = dict(
            fail_area=jnp.asarray(fa),
            fail_link=jnp.asarray(fl),
            src=jnp.asarray(enc.src),
            dst=jnp.asarray(enc.dst),
            w=jnp.asarray(w),
            edge_ok=jnp.asarray(enc.edge_ok),
            link_index=jnp.asarray(ctx["link_index"]),
            overloaded=jnp.asarray(overloaded),
            soft=jnp.asarray(enc.soft),
            roots=jnp.asarray(enc.roots),
            cand_area=jnp.asarray(dv.cand_area),
            cand_node=jnp.asarray(dv.cand_node),
            cand_ok=jnp.asarray(dv.cand_ok),
            drain_metric=jnp.asarray(dv.drain_metric),
            path_pref=jnp.asarray(dv.path_pref),
            source_pref=jnp.asarray(dv.source_pref),
            distance=jnp.asarray(dv.distance),
            cand_node_in_area=jnp.asarray(dv.cand_node_in_area),
        )
        with self._device_ctx(dev, pool), self._probe.phase(
            pipeline.SWEEP_SHARD_SOLVE, device=dev
        ):
            if pool is not None and dev is not None:
                d = pool.device(dev)
                kernel_args = {
                    k: jax.device_put(v, d) for k, v in kernel_args.items()
                }
            use, shortest, lanes, valid = jax.device_get(
                call_jit_guarded(
                    whatif_multi_area_tables,
                    max_degree=ctx["D"],
                    per_area_distance=ctx["inputs"].per_area_distance,
                    **kernel_args,
                )
            )
        if pool is not None and dev is not None:
            pool.note_dispatch(dev)
        self.num_device_solves += B
        self.counters.bump("sweep.device_solves", B)
        # merged route view (the multi-area engine's decode, counts only)
        m = np.where(valid, shortest, np.inf)
        m_star = m.min(axis=2)
        at_min = valid & (m == m_star[:, :, None])
        eff_lanes = lanes & at_min[:, :, :, None]
        merged = eff_lanes.sum(axis=(2, 3))
        req = np.max(np.where(use, dv.min_nexthop[None, :, :], 0), axis=2)
        route_ok = valid.any(axis=2) & (merged > 0) & (merged >= req)
        base = B  # the first pad row solves the unperturbed world
        out = []
        for s_i in range(B):
            diff = (route_ok[s_i] != route_ok[base]) | (
                route_ok[s_i]
                & route_ok[base]
                & (
                    (m_star[s_i] != m_star[base])
                    | (eff_lanes[s_i] != eff_lanes[base]).any(axis=(1, 2))
                )
            )
            withdrawn = int((route_ok[base] & ~route_ok[s_i]).sum())
            added = int((~route_ok[base] & route_ok[s_i]).sum())
            both = route_ok[base] & route_ok[s_i]
            inc = 0.0
            if both.any():
                d = m_star[s_i][both] - m_star[base][both]
                d = d[np.isfinite(d)]
                if len(d):
                    inc = float(max(float(d.max()), 0.0))
            out.append(
                (int(diff.sum()), withdrawn, added, round(inc, 3))
            )
        return out

    # -- commit -------------------------------------------------------------

    def _commit_shard(self, handle: _ShardHandle, rows: List[dict]) -> None:
        from openr_tpu.tracing import pipeline

        t0 = self.clock.now() if self.clock is not None else 0.0
        with self._probe.phase(pipeline.SWEEP_REDUCE):
            # ordering invariant: rows durable in the spill BEFORE the
            # checkpoint records the shard (docs/Developer_Guide.md)
            self.spill.spill_rows(rows)
            if self.commit_hook is not None:
                # riders (the protection store) persist their per-shard
                # artifacts under the same order: durable before the
                # checkpoint records the shard, so a crash between the
                # two re-runs the shard and overwrites idempotently
                self.commit_hook(handle.shard_id)
            self.checkpoint.commit_shard(
                handle.shard_id,
                {
                    "rows": len(rows),
                    "lo": self.shards[handle.shard_id][1],
                    "hi": self.shards[handle.shard_id][2],
                },
            )
            self.reducer.feed(rows)
        self.completed.add(handle.shard_id)
        self.counters.bump("sweep.shards_completed")
        self.counters.bump("sweep.scenarios_completed", len(rows))
        self.counters.bump("sweep.rows_spilled", len(rows))
        if self.clock is not None:
            self.counters.observe(
                "sweep.reduce_ms", (self.clock.now() - t0) * 1000.0
            )

    def _note_chip_failure(self, dev: Optional[int], exc: Exception) -> None:
        """A dispatch/drain on chip ``dev`` raised: quarantine it via
        the governor's streamed-failure path (probed recovery) and
        drop per-chip engine state — the re-pack dispatches on the
        survivors only."""
        ctx = self._ctx
        governor = ctx["inputs"].governor if ctx else None
        if governor is not None and dev is not None:
            try:
                governor.record_stream_failure(dev, exc)
            except Exception:  # noqa: BLE001 - never mask the original
                pass
        self.num_repacked_shards += 1
        self.counters.bump("sweep.repacked_shards")
        self._engines.clear()

    def _execute_with_repack(
        self, shard_id: int, exclude: List[int]
    ) -> Tuple[_ShardHandle, List[dict]]:
        """Dispatch + drain one shard, re-packing onto the next
        survivor when its chip fails mid-flight (the lost-shard-only
        re-pack)."""
        while True:
            ctx = self._context()
            pool = ctx["inputs"].pool
            dev = self._pick_device(pool, exclude=exclude)
            try:
                handle = self._dispatch_shard(shard_id, dev)
                rows = self._drain_shard(handle)
                return handle, rows
            except SweepError:
                raise
            except Exception as e:  # noqa: BLE001 - chip failure domain
                self._note_chip_failure(dev, e)
                if pool is None or dev is None:
                    raise SweepError(
                        f"shard {shard_id} failed with no device pool to "
                        f"re-pack on: {type(e).__name__}: {e}"
                    ) from e
                exclude.append(dev)

    # -- the run loop --------------------------------------------------------

    def run(
        self,
        yield_cb: Optional[Callable[[], None]] = None,
        stop_after_shards: Optional[int] = None,
    ) -> dict:
        """Execute every pending shard (streamed: up to ``inflight``
        shards in flight, FIFO commit).  ``yield_cb`` runs between
        shard commits (the service actor awaits the clock there);
        ``stop_after_shards`` commits that many then returns (the
        kill-and-resume tests and the bench's resume proof)."""
        inflight: "collections.deque" = collections.deque()
        committed_now = 0

        def commit(handle: _ShardHandle) -> None:
            nonlocal committed_now
            try:
                rows = self._drain_shard(handle)
            except Exception as e:  # noqa: BLE001 - chip failure domain
                self._note_chip_failure(handle.device_index, e)
                exclude = (
                    [handle.device_index]
                    if handle.device_index is not None
                    else []
                )
                handle, rows = self._execute_with_repack(
                    handle.shard_id, exclude
                )
            self._commit_shard(handle, rows)
            committed_now += 1

        try:
            for shard_id in self.pending_shards():
                if self.cancelled or (
                    stop_after_shards is not None
                    and committed_now + len(inflight) >= stop_after_shards
                ):
                    break
                while len(inflight) >= self.inflight_limit:
                    commit(inflight.popleft())
                    if yield_cb is not None:
                        yield_cb()
                ctx = self._context()
                pool = ctx["inputs"].pool
                dev = self._pick_device(pool)
                try:
                    inflight.append(self._dispatch_shard(shard_id, dev))
                except SweepError:
                    raise
                except Exception as e:  # noqa: BLE001 - chip failure
                    self._note_chip_failure(dev, e)
                    # drain what's safely in flight, then re-pack the
                    # failed shard onto the survivors
                    while inflight:
                        commit(inflight.popleft())
                    exclude = [dev] if dev is not None else []
                    handle, rows = self._execute_with_repack(
                        shard_id, exclude
                    )
                    self._commit_shard(handle, rows)
                    committed_now += 1
                    if yield_cb is not None:
                        yield_cb()
            while inflight:
                if not self.cancelled and (
                    stop_after_shards is None
                    or committed_now < stop_after_shards
                ):
                    commit(inflight.popleft())
                else:
                    # cancelled: drop uncommitted in-flight work (the
                    # checkpoint only ever records committed shards —
                    # exactly what a real kill leaves behind)
                    inflight.popleft()
        finally:
            if self.spill is not None:
                self.spill.seal()
        return self.status()

    # -- observability -------------------------------------------------------

    def status(self) -> dict:
        spill = self.spill.stats() if self.spill is not None else {}
        return {
            "sweep_id": self.sweep_id,
            "set_hash": self.set_hash,
            "scenarios_total": len(self.scenarios),
            "scenarios_completed": self.reducer.scenarios,
            "shards_total": len(self.shards),
            "shards_completed": len(self.completed),
            "resumed_shards": self.resumed_shards,
            "repacked_shards": self.num_repacked_shards,
            "device_solves": self.num_device_solves,
            "cancelled": self.cancelled,
            "generations_observed": len(self.generations_observed),
            "spill": spill,
        }

    def summary(self) -> dict:
        return {
            "sweep_id": self.sweep_id,
            "set_hash": self.set_hash,
            "complete": not self.pending_shards(),
            "summary": self.reducer.summary(),
            "summary_digest": self.reducer.summary_digest(),
        }
