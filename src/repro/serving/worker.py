"""Serving worker: the FaaS controller of the paper's Fig. 4, for models.

A *function* is a registered model variant (fine-tune / new head / adapter
merge) of a runtime *family* (architecture).  A request either hits a warm
instance (instance pool) or triggers a cold start through the snapshot
engine with the configured strategy (regular / reap / seuss / snapfaas− /
snapfaas / auto).  Execution runs the family's jitted step(s) on the
restored params — demand-paged leaves materialize the moment the request
path first touches them, exactly like REAP's runtime page faults.

The request path is typed (``Worker.invoke(InvocationRequest)``); the
legacy string-typed ``Worker.handle`` shim was removed after its promised
one-release deprecation window (see DESIGN.md migration notes).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AccessLog, ColdStartMetrics, RestoredInstance, ZygoteRegistry
from repro.core.planner import PAPER_C220G5, StorageModel, predict_demand_paged
from repro.core.tiers import PrefetchStats, TierSpec
from repro.core.restore import MaterializedArray
from repro.core.snapshot import flatten_pytree, resolve
from repro.kernels.snapshot_patch import patch_apply_op
from repro.models import Batch, Model
from repro.serving.api import (
    ColdStartOptions,
    InvocationRequest,
    InvocationResult,
    NpzSourceResolver,
    SourceResolver,
    Strategy,
    select_strategy,
)
from repro.serving.policy import InstancePool, PoolPolicy

PyTree = Any


@dataclass
class FunctionSpec:
    """What the developer 'uploads' (paper Fig. 3): variant params + which
    leaves its requests touch (handler signature) + a declared resolver for
    its source artifacts (``seuss``/``regular`` boot path).

    Two upload shapes:

    * ``variant`` — the complete parameter tree (legacy path; capture
      diffs it against the base, paying a full scan).
    * ``delta`` — only the arrays that differ from the family base
      (shared-base registration: capture cost and stored bytes are
      proportional to the delta; everything else is inherited by content
      address — ``ZygoteRegistry.register_from_base``).  When ``delta``
      is set, ``variant`` may be left empty.
    """

    name: str
    family: str
    variant: Dict[str, np.ndarray] = field(default_factory=dict)
    touched: Optional[List[str]] = None     # leaves a request reads (None=all)
    touched_rows: Dict[str, List[int]] = field(default_factory=dict)
    source_path: str = ""
    resolver: Optional[SourceResolver] = None  # default: NpzSourceResolver
    delta: Optional[Dict[str, np.ndarray]] = None  # shared-base upload
    exec_sleep_s: float = 0.0  # emulated handler I/O wait (load benches)


#: deprecated alias — results are InvocationResult now (same field names
#: plus ``requested``/``queue_s``/``pooled``/``worker_id``)
RequestResult = InvocationResult


class Worker:
    """One worker machine: zygote registry + instance pool + jitted families."""

    def __init__(self, root: str, *, pool_budget_bytes: int = 1 << 30,
                 chunk_bytes: int = 64 * 1024,
                 pool_policy: Optional[PoolPolicy] = None,
                 storage: StorageModel = PAPER_C220G5,
                 worker_id: int = 0,
                 tiers: Optional[TierSpec] = None,
                 prefetch_on_register: bool = True):
        self.registry = ZygoteRegistry(root, chunk_bytes=chunk_bytes,
                                       tiers=tiers)
        self.pool = InstancePool(pool_budget_bytes, policy=pool_policy)
        self.storage = storage              # deployment tier for Eq. 1 (AUTO)
        self.worker_id = worker_id
        # chaos: the tier spec's injector also drives worker-crash faults
        self.faults = tiers.faults if tiers is not None else None
        self.prefetch_on_register = prefetch_on_register
        self.models: Dict[str, Model] = {}
        self.specs: Dict[str, FunctionSpec] = {}
        self._fwd: Dict[str, callable] = {}
        # device-ready base pools / on-disk base images, per family.  Eagerly
        # initialised: the former getattr-lazy init raced register_function
        # against register_runtime (latent AttributeError).
        self._pool_dev: Dict[str, Dict[str, jax.Array]] = {}
        self._base_npz: Dict[str, str] = {}
        # Eq. 1 resolution cache for Strategy.AUTO: fn → (strategy, predictions)
        self._auto: Dict[str, Any] = {}
        self._lock = threading.RLock()

    # -- bootstrap (cluster-manager replication step) -------------------------

    def register_runtime(self, family: str, model: Model, base_params: PyTree,
                         fwd=None) -> None:
        """``fwd`` shares a jitted step across workers: a cluster broadcast
        passes one jit object fleet-wide so each (shape, family) compiles
        once per process, not once per worker — scale-up and steal targets
        would otherwise stall their first request behind a recompile."""
        self.models[family] = model
        flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
        self.registry.register_runtime(family, flat)
        if fwd is None:
            fwd = jax.jit(
                lambda p, tokens: model.logits(p, Batch(tokens=tokens)))
        self._fwd[family] = fwd
        # device-ready view of the base pool: shared (CoW-clean) leaves are
        # served zero-copy to every instance of the family — the runtime
        # analogue of the paper's mmap'd in-RAM base snapshot.
        pool = self.registry.pools[family]
        self._pool_dev[family] = {
            p: jnp.asarray(pool.get(p)) for p in self.registry.bases[family].arrays
        }
        # on-disk base image: what `regular` boots from (kernel+rootfs analog)
        base_path = os.path.join(self.registry.root, f"base-{family}.npz")
        np.savez(base_path, **{k.replace("/", "|"): v for k, v in flat.items()})
        self._base_npz[family] = base_path

    # -- function registration --------------------------------------------------

    def register_function(self, spec: FunctionSpec) -> None:
        if spec.delta is not None:
            # shared-base registration: capture only the delta; the full
            # manifest is synthesized by content address (no re-capture)
            rec = self.registry.register_from_base(
                spec.name, spec.family, spec.delta,
                source_path=spec.source_path,
            )
        else:
            rec = self.registry.register_function(
                spec.name, spec.family, spec.variant,
                source_path=spec.source_path,
            )
        # publish the spec only once the registry accepted the name — a
        # duplicate-registration ValueError must leave the worker untouched
        self.specs[spec.name] = spec
        if spec.resolver is None:
            spec.resolver = self._default_resolver(spec)
        # mock invocation under access tracking → WS files (paper Fig. 4).
        # Delta specs default to touching the whole effective tree (base
        # arrays + delta), matching what a full `variant` upload declares.
        if spec.touched is not None:
            touched = spec.touched
        elif spec.delta is not None:
            touched = set(self.registry.bases[spec.family].arrays) | set(spec.delta)
        else:
            touched = spec.variant
        log = AccessLog()
        for path in touched:
            log.touch(path)
        for path, rows in spec.touched_rows.items():
            log.touch_rows(path, rows)
        self.registry.generate_working_set(spec.name, log)
        # measure function-import compute once (SEUSS's memoized C term):
        # the planner's seuss/regular predictions are garbage without it.
        # Drop the artifact's page cache first — registration just wrote it,
        # and a cache-warm read would understate the cold import cost the
        # planner is modelling.
        if spec.source_path and os.path.exists(spec.source_path):
            fd = os.open(spec.source_path, os.O_RDONLY)
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            except (AttributeError, OSError):
                pass
            finally:
                os.close(fd)
        t0 = time.perf_counter()
        spec.resolver.load_source()
        rec.init_compute_s = time.perf_counter() - t0
        # shard-assignment prefetch: promote the function's WS into this
        # worker's warm tiers (RAM cache + local packs) so its first cold
        # start never pays the cold-tier read (REAP's record-and-prefetch,
        # applied across the storage hierarchy)
        if self.prefetch_on_register:
            self.prefetch_function(spec.name)
        # precompute the Eq. 1 table here, NOT on the first request — the
        # request path must never pay a planning pass inside its timed window
        with self._lock:
            self._auto.pop(spec.name, None)
        self._auto_entry(spec.name)

    def prefetch_function(self, fn: str, category: str = "ws") -> PrefetchStats:
        """Promote ``fn``'s working set into the warm tiers now (used at
        registration / shard assignment, and by the ``prefetch`` tier hint).
        ``category`` picks the eager set to warm (``ws``/``diff``/
        ``ws_full``/``full``) — warming a full-snapshot set also warms every
        sibling sharing those digests (residency is content-addressed)."""
        return self.registry.prefetch_working_set(fn, category)

    def record_function(
        self, fn: str, tokens: np.ndarray, *, n_profiles: int = 1,
    ) -> InvocationResult:
        """Profile ``fn`` REAP-style: run ``n_profiles`` forced-cold
        invocations in record mode, folding each access log into the
        function's persisted recording (the measured working set demand-paged
        restores prefetch).  Returns the last profile's result."""
        out: Optional[InvocationResult] = None
        for _ in range(max(1, n_profiles)):
            out = self.invoke(InvocationRequest(
                function=fn, tokens=np.asarray(tokens),
                options=ColdStartOptions(record=True, force_cold=True),
            ))
        assert out is not None
        return out

    def deregister_function(self, fn: str) -> int:
        """Remove ``fn`` everywhere on this worker: warm pool, spec, Eq. 1
        cache, snapshots.  Chunk payloads shared with the base or sibling
        functions survive (refcounted GC); returns bytes made unreachable."""
        self.pool.drop(fn)
        self.specs.pop(fn, None)
        with self._lock:
            self._auto.pop(fn, None)
        return self.registry.deregister_function(fn)

    def tier_stats(self) -> Dict[str, Any]:
        """This worker's storage-hierarchy counters (fleet metrics)."""
        return self.registry.store.tier_stats()

    def _default_resolver(self, spec: FunctionSpec) -> NpzSourceResolver:
        pool = self.registry.pools[spec.family]
        base = self.registry.bases[spec.family]
        own = spec.delta if spec.delta is not None else spec.variant
        dtypes = {p: m.dtype for p, m in base.arrays.items()}
        dtypes.update((p, str(v.dtype)) for p, v in own.items())
        return NpzSourceResolver(
            source_path=spec.source_path,
            base_path=self._base_npz.get(spec.family, ""),
            source_fallback=lambda: {k: np.array(v) for k, v in own.items()},
            base_fallback=lambda: {p: np.array(pool.get(p))
                                   for p in base.arrays},
            dtypes=dtypes,
        )

    # -- planner glue (Strategy.AUTO) ----------------------------------------

    def _auto_entry(self, fn: str):
        """Cached (ws, best strategy, prediction table, residency epoch)
        for ``fn``; rebuilt whenever the registry's working set object
        changed (e.g. a direct ``generate_working_set`` call — the registry
        clears its restore plans for the same reason) or tier movement
        (promotion/demotion/prefetch) shifted the eager set's residency
        split that a TieredStorageModel prices."""
        rec = self.registry.functions[fn]
        epoch = self.registry.store.residency_epoch
        with self._lock:
            entry = self._auto.get(fn)
            if entry is None or entry[0] is not rec.ws or entry[3] != epoch:
                sizes = self.registry.sizes(fn)
                best, preds = select_strategy(sizes, self.storage)
                # demand-paged variant of the winner: only priced when the
                # working set is *measured* (a real recording exists) — a
                # synthetic WS is not trustworthy enough to bet the B term on
                demand = False
                if sizes.has_recording and \
                        best.value in ("reap", "snapfaas", "snapfaas-"):
                    dp = predict_demand_paged(best.value, sizes, self.storage)
                    demand = dp.total < preds[best].total
                entry = (rec.ws, best, preds, epoch, demand)
                self._auto[fn] = entry
            return entry

    def resolve_strategy(self, fn: str, strategy: "Strategy | str") -> Strategy:
        """Concrete strategy for this request.  AUTO = the Eq. 1 argmin over
        the function's measured SnapshotSizes and this worker's StorageModel,
        cached per function until its working set changes."""
        s = Strategy.coerce(strategy)
        if s is not Strategy.AUTO:
            return s
        return self._auto_entry(fn)[1]

    def resolve_demand_paging(self, fn: str, opts: ColdStartOptions) -> bool:
        """Whether this request's cold start (if any) restores demand-paged.
        An explicit ``opts.demand_paging`` always wins; otherwise only
        :attr:`Strategy.AUTO` opts in, and only when the measured working
        set priced cheaper under Eq. 1 (see :func:`predict_demand_paged`)."""
        if opts.demand_paging is not None:
            return opts.demand_paging
        if Strategy.coerce(opts.strategy) is not Strategy.AUTO:
            return False
        return bool(self._auto_entry(fn)[4])

    def predicted_cost(self, fn: str, strategy: Strategy) -> float:
        """Predicted re-cold-start latency (s) — the GDSF residency cost."""
        _, best, preds, _, _ = self._auto_entry(fn)
        pred = preds.get(Strategy.coerce(strategy))
        return pred.total if pred is not None else preds[best].total

    # -- request path --------------------------------------------------------------

    def _maybe_device_patch(
        self, family: str, path: str, ma: MaterializedArray
    ) -> Optional[jax.Array]:
        """Apply this array's diff chunks to the device-resident base copy.

        The planned restore engine leaves patchable arrays as (packed diff
        rows + selection map) instead of assembling them on the host; here
        the ``snapshot_patch`` kernel fuses base ⊕ diff directly in device
        memory — base chunks never cross the host, diff chunks cross it once
        (the scatter-read).  Result is complete (every diff chunk applied),
        so it supersedes row-granular host materialization.  Cached per
        instance; invalidated by host writes.
        """
        if ma.patch is None or ma.written:
            return None
        if ma._dev is not None:
            return ma._dev
        base_dev = self._pool_dev.get(family, {}).get(path)
        if base_dev is None:
            return None
        meta = ma.meta
        itemsize = np.dtype(meta.dtype).itemsize
        c = meta.chunk_bytes // itemsize
        n = meta.num_chunks()
        total = meta.nbytes // itemsize
        rows2d = ma.patch.rows_2d()
        if rows2d.shape[0] == 0:
            return None  # nothing to patch (shouldn't happen: plan skips)
        diff2d = jnp.asarray(rows2d.view(np.dtype(meta.dtype)))
        flat = base_dev.reshape(-1)
        if n * c != total:  # partial tail chunk: pad base, slice after
            flat = jnp.pad(flat, (0, n * c - total))
        # the Pallas kernel on the chip; its jnp oracle elsewhere
        out = patch_apply_op(
            flat.reshape(n, c), diff2d, jnp.asarray(ma.patch.sel),
            mode="replace", use_kernel=jax.default_backend() == "tpu",
        )
        out = out.reshape(-1)[:total].reshape(meta.shape)
        ma._dev = out
        return out

    def _params_for(
        self, spec: FunctionSpec, inst: RestoredInstance,
        request_rows: Optional[Dict[str, np.ndarray]] = None,
        record_log: Optional[AccessLog] = None,
    ) -> PyTree:
        """Materialize exactly what this request touches.

        Gather-type leaves (embedding tables, expert banks — declared via
        ``touched_rows``) use row-granular demand materialization: only the
        chunks covering the request's rows fault in; everything else of the
        leaf keeps base content and is never read. Other touched leaves
        materialize fully. This is the exec-time half of the WS win.

        ``record_log`` is REAP's record mode: leaves served through the
        device shortcuts (zero-copy pool share, on-device patch) bypass the
        instrumented host materialization, so their touches are mirrored
        into the log here — row-granular where the serving contract is
        row-granular, full otherwise.  Host-path touches are logged by the
        MaterializedArrays themselves (``attach_access_log``)."""
        template = self.models[spec.family].param_shapes()
        rows = dict(spec.touched_rows)
        for k, v in (request_rows or {}).items():
            rows[k] = np.union1d(np.asarray(rows.get(k, []), np.int64), v)

        pool_dev = self._pool_dev.get(spec.family, {})

        def rec(t, prefix):
            if isinstance(t, dict):
                return {k: rec(v, f"{prefix}{k}/") for k, v in t.items()}
            path = prefix[:-1]
            ma = inst.arrays[path]
            if ma.state == "shared" and not ma.written and path in pool_dev:
                if record_log is not None:
                    record_log.touch(path)
                return pool_dev[path]  # zero-copy CoW share
            dev = self._maybe_device_patch(spec.family, path, ma)
            if dev is not None:
                if record_log is not None:
                    if path in rows:
                        record_log.touch_rows(path, rows[path])
                    else:
                        record_log.touch(path)
                return dev  # base ⊕ diff fused on device
            if path in rows:
                arr = ma.ensure_rows(rows[path], inst.metrics)
            else:
                arr = inst.value(path)
            return jnp.asarray(arr)

        return rec(template, "")

    def invoke(self, request: InvocationRequest) -> InvocationResult:
        """Typed request path: warm-pool lookup, cold start (with AUTO
        resolved through the planner), execution, pool re-admission."""
        fn = request.function
        opts = request.options
        if self.faults is not None:
            # injected worker crashes surface here, before any work — a
            # crashed worker fails every invocation until failed over
            self.faults.before_invoke(self.worker_id)
        spec = self.specs.get(fn)
        if spec is None:
            # requests queued behind a deregistration land here — a clear
            # error, never a read of reclaimed chunks
            raise KeyError(
                f"function {fn!r} is not registered on worker "
                f"{self.worker_id} (never registered, or deregistered)"
            )
        strategy = self.resolve_strategy(fn, opts.strategy)
        demand_paged = self.resolve_demand_paging(fn, opts)
        if opts.prefetch:
            # scheduler-style WS promotion into the warm tiers; deliberately
            # ahead of the timed window (the hint models a prefetch that
            # overlapped request arrival, e.g. on shard assignment)
            self.prefetch_function(fn, opts.prefetch_category)
        t0 = time.perf_counter()
        inst = None if opts.force_cold else self.pool.get(fn)
        cold = inst is None
        if cold:
            self.pool.drop(fn)
            loaders = self._loaders(spec)
            inst = self.registry.cold_start(
                fn, strategy.value,
                residual_init=lambda ds: {**ds, "kv_ready": True},
                engine=opts.engine,
                promote=opts.promote,
                demand_paged=demand_paged,
                **loaders,
            )
        boot = time.perf_counter() - t0

        te = time.perf_counter()
        record_log = AccessLog() if opts.record else None
        if record_log is not None:
            inst.attach_access_log(record_log)
        req_rows = {}
        if "embed/table" in spec.touched_rows or "embed/table" in spec.variant \
                or (spec.delta is not None and "embed/table" in spec.delta):
            req_rows["embed/table"] = np.unique(np.asarray(request.tokens))
        params = self._params_for(spec, inst, req_rows, record_log=record_log)
        logits = self._fwd[spec.family](params, jnp.asarray(request.tokens))
        logits.block_until_ready()
        if spec.exec_sleep_s > 0.0:
            # emulated handler I/O wait (FaaS handlers are mostly I/O
            # bound): a GIL-releasing sleep, so concurrent slots overlap
            # like real downstream calls would — the load benches use it
            # to keep service time parallelizable on small hosts
            time.sleep(spec.exec_sleep_s)
        exec_s = time.perf_counter() - te
        if inst.metrics is not None:
            inst.metrics.t_exec = exec_s
        if cold and inst.metrics is not None and inst.metrics.demand_paged:
            # recorded chunks still pending were prefetched for nothing
            inst.finalize_demand_paging()
        if record_log is not None:
            # fold this profile into the persisted recording; the WS swap
            # invalidates cached plans and this worker's Eq. 1 table, so
            # the pool re-admission below already prices the measured WS
            inst.attach_access_log(None)
            self.registry.record_access(fn, record_log)

        # charge host buffers AND cached patched device copies (ma._dev) to
        # the pool budget — a warm patchable instance pins a full-size
        # accelerator copy, so residency must reflect it (Fig. 7's trade)
        nbytes = sum(
            a.meta.nbytes * (2 if a._dev is not None else 1)
            for a in inst.arrays.values()
        )
        pooled = self.pool.put(fn, inst, nbytes,
                               cost=self.predicted_cost(fn, strategy))
        m = inst.metrics if cold else None
        return InvocationResult(
            function=fn, cold=cold, requested=Strategy.coerce(opts.strategy),
            strategy=strategy,
            latency_s=time.perf_counter() - t0, boot_s=boot if cold else 0.0,
            exec_s=exec_s, pooled=pooled, worker_id=self.worker_id,
            metrics=m,
            output=np.asarray(logits[:, -1, :8]),
            fault_recovered=bool(
                m is not None and (m.read_retries or m.repaired_chunks)
            ),
        )

    def _loaders(self, spec: FunctionSpec):
        """Registry-facing adapters over the spec's declared SourceResolver
        (``seuss``/``regular`` boot from storage artifacts — the costs those
        designs cannot memoize, paper §2.2)."""
        resolver = spec.resolver or self._default_resolver(spec)
        return {"source_loader": resolver.load_source,
                "base_loader": resolver.load_base}

    def source_files(self, fn: str) -> list:
        """On-disk source artifacts of a function (for cache dropping)."""
        out = []
        spec = self.specs[fn]
        if spec.source_path:
            out.append(spec.source_path)
        p = self._base_npz.get(spec.family)
        if p:
            out.append(p)
        return out
