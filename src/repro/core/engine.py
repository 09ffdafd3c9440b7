"""The execution-plan engine: plan-then-execute for the unified compute unit.

The paper chooses the compute-unit configuration *once* per network from the
hardware specification, then runs every conv/FC layer through the resulting
template.  This module is that split for the TPU plane:

* :class:`PlanRegistry` — the durable DSE artifact (DESIGN.md §6).
  ``default_block_for`` is an exhaustive grid search over (bm, bn, bk); the
  registry guarantees it runs **once per GEMM shape per hardware spec**, with
  hit/miss counters so tests (and ops dashboards) can assert no re-search
  happens on the hot path.  Beyond the in-process memo the registry
  *persists*: ``save``/``load`` round-trip GEMM blocks and direct-conv
  (τ, tile_rows, tile_cols, halo_mode) choices — including cached no-fit
  sentinels — as versioned
  JSON keyed by (shape..., :class:`~repro.core.tiling.TpuSpec`), and
  ``measure_and_pin`` overwrites the analytic choice with a measured-time
  winner (per-entry ``source`` provenance: ``analytic`` vs ``measured``).
  Registries are process-global per spec (:func:`plan_cache_for`);
  :func:`save_plan_store`/:func:`load_plan_store` serialize them all to the
  ``REPRO_PLAN_STORE`` path so serving restarts and CI benchmark runs
  warm-start with zero grid searches.

* :class:`ConvPlan` / :class:`GemmPlan` — per-layer execution plans: which
  kernel route a conv takes (direct Pallas conv vs im2col GEMM), the
  output-channel tile τ and spatial row tile of the direct route, and the
  pre-resolved Pallas block for GEMM routes.  Planning is sharding-aware:
  ``Engine.plan_gemm``/``plan_conv`` accept an optional mesh + PartitionSpec
  and plan the *local per-shard* shapes (M over data axes, N over model).

* :class:`Engine` — executes plans.  It owns backend dispatch (xla / pallas
  float / q16 fixed point), the conv routing decision (DESIGN.md §2), and
  epilogue fusion (bias + ReLU + optional output quantization pushed into
  the kernels' write-back, DESIGN.md §3).

:class:`~repro.core.template.Template` delegates its ``matmul`` / ``linear``
/ ``conv2d`` API here; networks (``models/cnn.py``) compile a
``NetworkPlan`` once and reuse it every step.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import json
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from . import dse
from .quantization import (
    NumericsPolicy,
    QFormat,
    QTensor,
    dequantize,
    fake_quant_fmt,
    quantize,
    quantize_qtensor,
)
from .tiling import MatmulBlock, TPU_V5E, TpuSpec, clamp_block, space_to_depth_geometry

__all__ = [
    "PLAN_STORE_ENV",
    "PLAN_STORE_FORMAT",
    "PLAN_STORE_VERSION",
    "PLAN_STORE_COMPAT_VERSIONS",
    "PlanCache",
    "PlanRegistry",
    "PlanStoreError",
    "ConvPlan",
    "GemmPlan",
    "PrecisionChoice",
    "Engine",
    "batch_rungs",
    "bucket_for",
    "default_plan_store_path",
    "validate_policy",
    "load_plan_store",
    "plan_cache_for",
    "plan_store_stats",
    "register_plan_store",
    "reset_plan_caches",
    "save_plan_store",
    "warm_start_plan_store",
]


def bucket_for(length: int, ladder: Sequence[int]) -> Optional[int]:
    """The bucket-ladder rule: the smallest ladder entry >= length.

    The serve scheduler pads every prefill up to a rung of a small ladder so
    the engine sees a handful of fixed GEMM shapes — each planned once,
    registry hits forever after — instead of one shape per prompt length.
    Returns None when the length exceeds every rung (the request cannot be
    admitted at this ladder).
    """
    if length < 0:
        raise ValueError(f"negative length {length}")
    best = None
    for rung in ladder:
        if rung >= length and (best is None or rung < best):
            best = rung
    return best


def batch_rungs(slots: int) -> tuple:
    """Batch-size ladder for coalesced (B, L) prefill launches.

    Powers of two up to ``slots`` plus ``slots`` itself: a tick's pending
    prefills for one bucket rung are padded up to the smallest batch rung
    >= their count, so the engine sees |batch_rungs| x |ladder| prefill GEMM
    shapes total — each planned and traced once at warmup — instead of a
    fresh shape per admission-count.
    """
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    rungs = set()
    b = 1
    while b < slots:
        rungs.add(b)
        b *= 2
    rungs.add(slots)
    return tuple(sorted(rungs))


# ---------------------------------------------------------------------------
# plan registry (memoized DSE, persistent + measured-time overwrite)
# ---------------------------------------------------------------------------

PLAN_STORE_FORMAT = "repro-plan-store"
#: v2 (PR 8) added the ConvTileChoice column-tiling fields (tile_cols,
#: col_tiles, halo_mode).  v3 (PR 10) added the per-layer precision section
#: (the drift-aware int8/int16 grid assignments, DESIGN.md §11).  v4 keys a
#: conv entry by the stride-1 conv the kernel runs, without the layer's
#: stride (a strided layer plans over its space-to-depth input).  Older
#: stores still load leniently: v2 and v3 keep their gemm entries and the
#: conv entries of stride-1 layers (v2 has no precision pins); their strided
#: conv entries, planned over strided windows, are dropped and re-plan; v1
#: keeps gemm only — its pre-column-tiling conv entries are dropped so those
#: layers re-plan against the three-regime DSE instead of raising
#: PlanStoreError.
PLAN_STORE_VERSION = 4
PLAN_STORE_COMPAT_VERSIONS = (1, 2, 3)
#: Env var naming the default persisted plan-store path.  When set, the
#: launch drivers (serve/train) and the benchmark harness warm-start from it
#: and write newly planned shapes back on exit.
PLAN_STORE_ENV = "REPRO_PLAN_STORE"


class PlanStoreError(ValueError):
    """A plan store file is unreadable, corrupted, or version-mismatched."""


@dataclasses.dataclass(frozen=True)
class PrecisionChoice:
    """One pinned per-layer activation grid (the precision DSE's output).

    ``fmt`` is the layer's *input* activation format (int8 rung or the
    network's base int16 grid); ``drift`` records the measured solo-flip
    argmax agreement that justified the choice (None for analytic pins).
    """

    fmt: QFormat
    drift: Optional[float] = None


def _timing_device():
    """The device :meth:`PlanRegistry.measure_and_pin` times on: a TPU.
    Anywhere else the kernels run interpreted and a timing says nothing
    about the chip, so this raises."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"measure_and_pin times compiled kernels on a TPU; this process "
            f"computes on {dev.platform!r}, where Pallas kernels run interpreted"
        )
    return dev


def _spec_to_doc(spec: TpuSpec) -> dict:
    return dataclasses.asdict(spec)


def _spec_from_doc(doc: dict) -> TpuSpec:
    try:
        return TpuSpec(**doc)
    except TypeError as err:
        raise PlanStoreError(f"unrecognized TpuSpec fields in plan store: {err}") from err


class PlanRegistry:
    """Memoized DSE selection: GEMM blocks and direct-conv tile configs.

    GEMM blocks are keyed by (m, n, k, hardware spec); direct-conv
    (τ, tile_rows, tile_cols, halo_mode) choices by the layer geometry +
    spec.  ``misses`` counts
    actual grid searches performed (either kind); ``hits`` counts lookups
    served from the registry.  A repeated shape must cost exactly one search
    for the lifetime of the registry — or *zero* when the entry was
    pre-loaded from a persisted store (:meth:`load`) or pinned by the
    measured-time autotuner (:meth:`measure_and_pin`).  Every entry carries
    ``source`` provenance: ``"analytic"`` (grid-search score) or
    ``"measured"`` (timed kernel launches).  ``skinny`` counts the GEMM
    searches that took the skinny-M branch (:func:`dse.skinny_m`).
    """

    def __init__(self) -> None:
        self._blocks: dict = {}
        self._conv_tiles: dict = {}
        self._precision: dict = {}
        self._block_src: dict = {}
        self._conv_src: dict = {}
        self._prec_src: dict = {}
        self.hits = 0
        self.misses = 0
        self.skinny = 0

    # -- lookups (memoized searches) ----------------------------------------

    def block_for(self, m: int, n: int, k: int, spec: TpuSpec = TPU_V5E) -> MatmulBlock:
        key = (m, n, k, spec)
        blk = self._blocks.get(key)
        if blk is None:
            self.misses += 1
            if dse.skinny_m(m, spec):
                self.skinny += 1
            blk = dse.default_block_for(m, n, k, spec)
            self._blocks[key] = blk
            self._block_src[key] = "analytic"
        else:
            self.hits += 1
        return blk

    def conv_tile_for(
        self,
        hp: int, wp: int, cin: int, kh: int, kw: int, ho: int, wo: int,
        cout: int, in_bytes: int, spec: TpuSpec = TPU_V5E,
    ):
        """Memoized :func:`dse.default_conv_tile_for` (None = no fit cached)."""
        key = (hp, wp, cin, kh, kw, ho, wo, cout, in_bytes, spec)
        if key in self._conv_tiles:
            self.hits += 1
            return self._conv_tiles[key]
        self.misses += 1
        choice = dse.default_conv_tile_for(
            hp, wp, cin, kh, kw, ho, wo, cout, spec, in_bytes
        )
        self._conv_tiles[key] = choice
        self._conv_src[key] = "analytic"
        return choice

    # -- per-layer precision pins (the drift-aware DSE, DESIGN.md §11) -------

    def precision_for(
        self, net: str, layer: str, spec: TpuSpec = TPU_V5E
    ) -> Optional[PrecisionChoice]:
        """The pinned activation grid for one named layer, or None.

        A found pin counts as a hit; a miss is *not* ticked here — the
        precision search is a whole-network drift sweep, so the single miss
        is charged by :meth:`pin_precision` when the sweep actually ran
        (``searched=True``).  A warm restart therefore replays every layer
        as hits with zero misses (``REPRO_PLAN_ASSERT_WARM``).
        """
        ent = self._precision.get((net, layer, spec))
        if ent is not None:
            self.hits += 1
        return ent

    def pin_precision(
        self,
        net: str,
        layer: str,
        fmt: QFormat,
        *,
        drift: Optional[float] = None,
        spec: TpuSpec = TPU_V5E,
        source: str = "measured",
        searched: bool = True,
    ) -> PrecisionChoice:
        """Record one layer's chosen grid (``source: measured`` provenance —
        the choice came from a real drift sweep, not an analytic model)."""
        if searched:
            self.misses += 1
        choice = PrecisionChoice(fmt=fmt, drift=drift)
        key = (net, layer, spec)
        self._precision[key] = choice
        self._prec_src[key] = source
        return choice

    def precision_plan(self, net: str, spec: TpuSpec = TPU_V5E) -> dict:
        """All pinned (layer -> QFormat) choices for one network (no
        counter ticks — this is an inspection/report helper)."""
        return {
            key[1]: ent.fmt
            for key, ent in self._precision.items()
            if key[0] == net and key[2] == spec
        }

    # -- measured-time autotune ---------------------------------------------

    def measure_and_pin(
        self,
        m: int,
        n: int,
        k: int,
        spec: TpuSpec = TPU_V5E,
        *,
        candidates: Optional[Sequence[MatmulBlock]] = None,
        top_k: int = 3,
        reps: int = 2,
        dtype=jnp.float32,
    ) -> MatmulBlock:
        """Time the top-K analytic candidates with real kernel launches and
        overwrite the registry entry with the fastest (``source: measured``).

        The timings are of compiled kernels on a TPU: on any other platform
        the kernels would run interpreted, so this raises there
        (:func:`_timing_device`) rather than pin an interpreter timing.
        """
        from repro.kernels import ops as kops

        _timing_device()
        if candidates is None:
            ranked = dse.explore_tpu_block(m, n, k, spec, top=top_k)
            candidates = [blk for blk, _ in ranked]
        if not candidates:
            candidates = [clamp_block(m, n, k, MatmulBlock(128, 128, 128), spec)]
        key0 = jax.random.PRNGKey(0)
        x = jax.random.normal(key0, (m, k), dtype) * 0.3
        w = jax.random.normal(jax.random.fold_in(key0, 1), (k, n), dtype) * 0.3
        best, best_t = None, float("inf")
        for blk in candidates:
            run = lambda: jax.block_until_ready(
                kops.matmul_fp(x, w, block=blk, vmem_limit_bytes=spec.vmem_bytes)
            )
            run()  # compile / first-touch outside the timed region
            t0 = time.perf_counter()
            for _ in range(reps):
                run()
            t = (time.perf_counter() - t0) / reps
            if t < best_t:
                best, best_t = blk, t
        key = (m, n, k, spec)
        self._blocks[key] = best
        self._block_src[key] = "measured"
        return best

    # -- provenance / stats --------------------------------------------------

    def source_for(self, m: int, n: int, k: int, spec: TpuSpec = TPU_V5E) -> Optional[str]:
        return self._block_src.get((m, n, k, spec))

    def stats(self) -> dict:
        """Separate GEMM-block and conv-tile counts (+ counters, provenance)."""
        measured = sum(1 for s in self._block_src.values() if s == "measured")
        measured += sum(1 for s in self._conv_src.values() if s == "measured")
        measured += sum(1 for s in self._prec_src.values() if s == "measured")
        return {
            "gemm_blocks": len(self._blocks),
            "conv_tiles": len(self._conv_tiles),
            "precision": len(self._precision),
            "hits": self.hits,
            "misses": self.misses,
            "measured": measured,
        }

    @contextlib.contextmanager
    def scope(self, into: Optional[dict] = None):
        """Count hits/misses attributable to one region (per-bucket stats).

        Yields a dict that, on exit, holds the hit/miss *delta* incurred
        inside the with-block (and ``skinny``, the skinny-M GEMM searches
        among the misses); when ``into`` is given the hit/miss delta is also
        accumulated there (``into["hits"] += ...``).  The scheduler wraps
        each bucket's prefill trace and the decode trace in a scope so its
        stats line can attribute plan work to individual ladder rungs.
        """
        delta = {"hits": 0, "misses": 0, "skinny": 0}
        h0, m0, s0 = self.hits, self.misses, self.skinny
        try:
            yield delta
        finally:
            delta["hits"] = self.hits - h0
            delta["misses"] = self.misses - m0
            delta["skinny"] = self.skinny - s0
            if into is not None:
                into["hits"] = into.get("hits", 0) + delta["hits"]
                into["misses"] = into.get("misses", 0) + delta["misses"]

    def __len__(self) -> int:
        return len(self._blocks) + len(self._conv_tiles) + len(self._precision)

    def clear(self) -> None:
        self._blocks.clear()
        self._conv_tiles.clear()
        self._precision.clear()
        self._block_src.clear()
        self._conv_src.clear()
        self._prec_src.clear()
        self.hits = 0
        self.misses = 0
        self.skinny = 0

    # -- serialization (DESIGN.md §6 schema) ---------------------------------

    def to_doc(self) -> dict:
        """The registry as a versioned, JSON-serializable document."""
        specs: list = []
        spec_ix: dict = {}

        def six(spec: TpuSpec) -> int:
            if spec not in spec_ix:
                spec_ix[spec] = len(specs)
                specs.append(_spec_to_doc(spec))
            return spec_ix[spec]

        def order(key):  # deterministic artifact: sort by spec then shape
            return (repr(key[-1]), key[:-1])

        gemm = [
            {
                "spec": six(key[3]),
                "key": list(key[:3]),
                "block": [blk.bm, blk.bn, blk.bk],
                "source": self._block_src.get(key, "analytic"),
            }
            for key, blk in sorted(self._blocks.items(), key=lambda kv: order(kv[0]))
        ]
        conv = [
            {
                "spec": six(key[-1]),
                "key": list(key[:-1]),
                "choice": None if choice is None else dse.conv_choice_to_doc(choice),
                "source": self._conv_src.get(key, "analytic"),
            }
            for key, choice in sorted(self._conv_tiles.items(), key=lambda kv: order(kv[0]))
        ]
        precision = [
            {
                "spec": six(key[-1]),
                "key": list(key[:-1]),  # [net, layer]
                "fmt": [ent.fmt.int_bits, ent.fmt.frac_bits, ent.fmt.total_bits],
                "drift": ent.drift,
                "source": self._prec_src.get(key, "measured"),
            }
            for key, ent in sorted(self._precision.items(), key=lambda kv: order(kv[0]))
        ]
        return {
            "format": PLAN_STORE_FORMAT,
            "version": PLAN_STORE_VERSION,
            "specs": specs,
            "gemm": gemm,
            "conv": conv,
            "precision": precision,
        }

    def merge_doc(self, doc: dict) -> int:
        """Merge a :meth:`to_doc` document into this registry.

        Loaded entries overwrite existing ones and count as neither hits nor
        misses (a later lookup of a loaded entry is a hit).  Returns the
        number of entries merged; raises :class:`PlanStoreError` on any
        format/structure mismatch or an *unknown* version.  A known older
        version (``PLAN_STORE_COMPAT_VERSIONS``) loads leniently: gemm
        entries merge from every compat version (their schema is unchanged),
        conv entries merge from v2+ (v1's pre-column-tiling docs are dropped
        so those layers re-plan under the current DSE; v2/v3 keys lose their
        stride, and their strided entries are dropped), and precision pins
        merge from v3+ (older stores simply have none, so those networks
        re-run the drift sweep) — a warm fleet store survives the upgrade
        instead of crashing the loader.
        """
        blocks: dict = {}
        block_src: dict = {}
        conv_tiles: dict = {}
        conv_src: dict = {}
        precision: dict = {}
        prec_src: dict = {}
        try:
            if doc.get("format") != PLAN_STORE_FORMAT:
                raise PlanStoreError(
                    f"not a plan store (format={doc.get('format')!r}, "
                    f"want {PLAN_STORE_FORMAT!r})"
                )
            version = doc.get("version")
            if version != PLAN_STORE_VERSION and version not in PLAN_STORE_COMPAT_VERSIONS:
                raise PlanStoreError(
                    f"plan store version {version!r} does not match "
                    f"this build's version {PLAN_STORE_VERSION}"
                )
            legacy_conv = version < 2  # pre-column-tiling conv docs
            specs = [_spec_from_doc(d) for d in doc["specs"]]

            def spec_at(ix) -> TpuSpec:
                if not isinstance(ix, int) or not 0 <= ix < len(specs):
                    raise PlanStoreError(f"bad spec index {ix!r}")
                return specs[ix]

            for e in doc["gemm"]:
                if len(e["key"]) != 3 or len(e["block"]) != 3:
                    raise PlanStoreError(
                        f"bad gemm entry: key={e['key']!r} block={e['block']!r}"
                    )
                m, nn, k = (int(v) for v in e["key"])
                key = (m, nn, k, spec_at(e["spec"]))
                blocks[key] = MatmulBlock(*(int(v) for v in e["block"]))
                block_src[key] = str(e.get("source", "analytic"))
            for e in doc["conv"]:
                if legacy_conv:
                    # pre-column-tiling choice docs lack (tile_cols,
                    # halo_mode); dropping them re-plans those layers
                    continue
                shape = [int(v) for v in e["key"]]
                if version < 4:
                    # v2/v3 keys carry the layer's stride (index 8); a
                    # strided entry was planned over strided windows
                    if len(shape) != 10:
                        raise PlanStoreError(f"bad conv key of length {len(shape)}")
                    if shape.pop(8) != 1:
                        continue
                key = tuple(shape) + (spec_at(e["spec"]),)
                if len(key) != 10:
                    raise PlanStoreError(f"bad conv key of length {len(key)}")
                choice = e["choice"]
                conv_tiles[key] = (
                    None if choice is None else dse.conv_choice_from_doc(choice)
                )
                conv_src[key] = str(e.get("source", "analytic"))
            for e in doc.get("precision", ()) if version >= 3 else ():
                if len(e["key"]) != 2 or len(e["fmt"]) != 3:
                    raise PlanStoreError(
                        f"bad precision entry: key={e['key']!r} fmt={e['fmt']!r}"
                    )
                net, layer = (str(v) for v in e["key"])
                key = (net, layer, spec_at(e["spec"]))
                ib, fb, tb = (int(v) for v in e["fmt"])
                drift = e.get("drift")
                precision[key] = PrecisionChoice(
                    fmt=QFormat(ib, fb, tb),
                    drift=None if drift is None else float(drift),
                )
                prec_src[key] = str(e.get("source", "measured"))
        except PlanStoreError:
            raise
        except (KeyError, IndexError, TypeError, ValueError) as err:
            raise PlanStoreError(f"corrupted plan store: {err!r}") from err
        # commit only after the whole document validated — a rejected store
        # must never leave a half-merged registry behind
        self._merge_entries(self._blocks, self._block_src, blocks, block_src)
        self._merge_entries(self._conv_tiles, self._conv_src, conv_tiles, conv_src)
        self._merge_entries(self._precision, self._prec_src, precision, prec_src)
        return len(blocks) + len(conv_tiles) + len(precision)

    @staticmethod
    def _merge_entries(dst_vals: dict, dst_src: dict, vals: dict, srcs: dict) -> None:
        """Merge entry maps; an existing *measured* pin outranks an incoming
        analytic choice (measured-time autotune results are expensive and
        must never be silently downgraded by a concurrent analytic writer)."""
        for key, val in vals.items():
            src = srcs.get(key, "analytic")
            if dst_src.get(key) == "measured" and src != "measured":
                continue
            dst_vals[key] = val
            dst_src[key] = src

    def merge_from(self, other: "PlanRegistry", spec: Optional[TpuSpec] = None) -> None:
        """Copy ``other``'s entries into this registry (incoming wins on
        conflict, except that measured pins outrank analytic choices);
        ``spec`` restricts the copy to entries keyed by one hardware spec.
        Counters are untouched — merges are not lookups."""
        blocks = {
            k: v for k, v in other._blocks.items() if spec is None or k[3] == spec
        }
        tiles = {
            k: v for k, v in other._conv_tiles.items() if spec is None or k[-1] == spec
        }
        prec = {
            k: v for k, v in other._precision.items() if spec is None or k[-1] == spec
        }
        self._merge_entries(self._blocks, self._block_src, blocks, other._block_src)
        self._merge_entries(self._conv_tiles, self._conv_src, tiles, other._conv_src)
        self._merge_entries(self._precision, self._prec_src, prec, other._prec_src)

    def specs(self) -> set:
        """The distinct hardware specs this registry holds entries for."""
        return (
            {key[3] for key in self._blocks}
            | {key[-1] for key in self._conv_tiles}
            | {key[-1] for key in self._precision}
        )

    def gemm_shapes(self, spec: TpuSpec = TPU_V5E) -> list:
        """The distinct (m, n, k) GEMM keys planned for ``spec``, sorted.

        Lets a mesh-mode scheduler warmup re-plan every GEMM it just traced
        at its *local per-shard* shape (``Engine.plan_gemm(mesh=...)``)
        without re-deriving the model's layer dimensions."""
        return sorted(key[:3] for key in self._blocks if key[3] == spec)

    def save(self, path: str) -> str:
        """Write the registry as versioned JSON (stage-then-commit atomic).

        The staged temp file is fsync'd before the ``os.replace`` commit so
        a crash after the rename cannot leave the store pointing at
        unflushed data; a crash *before* it leaves the previous store
        untouched (plus a stale ``{path}.tmp.{pid}`` — garbage-collected by
        the next :func:`save_plan_store` under the merge lock)."""
        doc = self.to_doc()
        tmp = f"{path}.tmp.{os.getpid()}"
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        try:  # make the rename itself durable
            dfd = os.open(parent, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass
        return path

    def load(self, path: str) -> int:
        """Merge a persisted store into this registry; returns entries loaded."""
        try:
            with open(path) as f:
                doc = json.load(f)
        except OSError as err:
            raise PlanStoreError(f"cannot read plan store {path!r}: {err}") from err
        except json.JSONDecodeError as err:
            raise PlanStoreError(f"corrupted plan store {path!r}: {err}") from err
        if not isinstance(doc, dict):
            raise PlanStoreError(f"corrupted plan store {path!r}: not a JSON object")
        return self.merge_doc(doc)


#: Back-compat alias — PR 1/2 code and tests constructed PlanCache directly.
PlanCache = PlanRegistry


_PLAN_CACHES: dict = {}
#: Higher-level plan memos (e.g. models/cnn.py's NetworkPlan table) register
#: themselves here so reset_plan_caches() empties them too.
_EXTRA_PLAN_STORES: list = []


def plan_cache_for(spec: TpuSpec = TPU_V5E) -> PlanRegistry:
    """The process-global plan registry for a hardware spec."""
    cache = _PLAN_CACHES.get(spec)
    if cache is None:
        cache = _PLAN_CACHES[spec] = PlanRegistry()
    return cache


def register_plan_store(store: dict) -> None:
    """Register a derived plan memo to be emptied by :func:`reset_plan_caches`.

    Registrations are deduplicated by identity: a module re-registering its
    (module-level) memo — e.g. via importlib.reload — must not grow the list.
    """
    if any(s is store for s in _EXTRA_PLAN_STORES):
        return
    _EXTRA_PLAN_STORES.append(store)


def reset_plan_caches() -> None:
    """Drop all cached plans (tests / reconfiguration).

    Caches are cleared in place — live Engines keep their (now empty)
    PlanRegistry object, so their stats stay consistent with the global one.
    """
    for cache in _PLAN_CACHES.values():
        cache.clear()
    for store in _EXTRA_PLAN_STORES:
        store.clear()


# ---------------------------------------------------------------------------
# persisted plan store (all per-spec registries <-> one JSON file)
# ---------------------------------------------------------------------------


def default_plan_store_path() -> Optional[str]:
    """The ``REPRO_PLAN_STORE`` path, or None when unset/empty."""
    return os.environ.get(PLAN_STORE_ENV) or None


@contextlib.contextmanager
def _store_write_lock(path: str):
    """Serialize the read-merge-write save cycle across processes sharing one
    store (serve + train, parallel CI shards) via an advisory flock on a
    sidecar file.  Best-effort: on platforms without fcntl the save falls
    back to the unserialized (atomic-replace) write."""
    try:
        import fcntl
    except ImportError:  # pragma: no cover - non-POSIX platforms
        yield
        return
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(f"{path}.lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lockf, fcntl.LOCK_UN)


def save_plan_store(path: Optional[str] = None) -> str:
    """Serialize every process-global registry into one versioned JSON file.

    Entries already on disk are merged in first (this process's plans win on
    conflict), so concurrent writers sharing one store — e.g. serve + train,
    or two CI shards — append to rather than overwrite each other's work.
    An unusable on-disk store is simply replaced.
    """
    path = path or default_plan_store_path()
    if path is None:
        raise ValueError(
            f"no plan-store path given and {PLAN_STORE_ENV} is unset"
        )
    with _store_write_lock(path):
        merged = PlanRegistry()
        if os.path.exists(path):
            try:
                merged.load(path)
            except PlanStoreError:
                pass
        for reg in _PLAN_CACHES.values():
            merged.merge_from(reg)
        out = merged.save(path)
        # gc temp litter from writers that died inside the stage->commit
        # window; safe under the merge lock (every store writer stages its
        # temp file while holding it, so any `{path}.tmp.*` sibling we can
        # see here is an orphan)
        for stale in glob.glob(f"{path}.tmp.*"):
            try:
                os.unlink(stale)
            except OSError:
                pass
        return out


def load_plan_store(path: Optional[str] = None, *, missing_ok: bool = False) -> int:
    """Load a persisted store and distribute entries to the per-spec global
    registries.  Returns the number of entries loaded (0 when ``missing_ok``
    and the file does not exist)."""
    path = path or default_plan_store_path()
    if path is None:
        raise ValueError(
            f"no plan-store path given and {PLAN_STORE_ENV} is unset"
        )
    if missing_ok and not os.path.exists(path):
        return 0
    stage = PlanRegistry()
    n = stage.load(path)
    for spec in stage.specs():
        plan_cache_for(spec).merge_from(stage, spec)
    return n


def warm_start_plan_store(path: Optional[str] = None) -> tuple[Optional[str], int]:
    """Warm start from ``path`` (default: ``REPRO_PLAN_STORE``) if it exists.

    The one warm-start entry point the launch drivers and the benchmark
    harness share.  Returns (path, entries_loaded); (None, 0) when neither a
    path nor the env var names a store.  A corrupted or version-mismatched
    store is *not* fatal here — a warm-start cache must never be a startup
    single point of failure, so the error is reported and the process cold
    starts (strict loading stays available via :func:`load_plan_store`; the
    CI warm gate still fails because zero entries load).
    """
    path = path or default_plan_store_path()
    if path is None:
        return None, 0
    try:
        return path, load_plan_store(path, missing_ok=True)
    except PlanStoreError as err:
        import warnings

        warnings.warn(f"ignoring unusable plan store {path!r}: {err}")
        return path, 0


def plan_store_stats() -> dict:
    """Aggregate :meth:`PlanRegistry.stats` across all per-spec registries."""
    total = {
        "gemm_blocks": 0, "conv_tiles": 0, "precision": 0,
        "hits": 0, "misses": 0, "measured": 0,
    }
    for reg in _PLAN_CACHES.values():
        for k, v in reg.stats().items():
            total[k] += v
    return total


# ---------------------------------------------------------------------------
# per-layer plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Pre-resolved plan for one GEMM shape.

    (m, n, k) is the shape the kernel *executes* — under a mesh that is the
    local per-shard shape, and ``logical`` records the global shape it was
    derived from (empty when planned unsharded or the mesh splits nothing).
    """

    m: int
    n: int
    k: int
    block: Optional[MatmulBlock]  # None for the xla backend
    logical: tuple = ()


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """Pre-resolved plan for one conv layer.

    route: "direct" (Pallas direct conv), "im2col" (GEMM fallback), or "xla".
    tau: output-channel tile of the direct kernel (0 on GEMM routes).
    block: Pallas block for the im2col GEMM (None otherwise).
    gemm: the layer's equivalent (m, n, k) GEMM shape.
    vmem_bytes: modeled VMEM working set of the chosen route's grid step.
    tile_rows: direct-route output rows per grid step (0 = whole image).
    spatial_tiles: ceil(Ho / tile_rows) — grid steps along the row axis.
    tile_cols: direct-route output columns per grid step (0 = full width;
        only the DMA-halo regime tiles this axis).
    col_tiles: ceil(Wo / tile_cols) — grid steps along the column axis.
    halo_mode: tiled-input regime — "none" (untiled), "two_block" (blocked
        successor reads), or "dma" (exact-window async copies); see
        kernels/conv2d.py and DESIGN.md §2.
    halo: cross-chip spatial-sharding seam (a ``SpatialHalo``, DESIGN.md
        §10) — when set, the layer executes per H slab in the slab-major
        (S, N, lx, W, C) layout via :meth:`Engine._conv2d_spatial`; ``pad``
        is then 0 (the halo exchange's zero fill *is* the H padding, and
        the executor pre-pads W by ``halo.pad``).

    A strided direct conv runs as the stride-1 conv it equals over its
    space-to-depth input (:attr:`space_to_depth`); τ, tiles and VMEM are
    those of that stride-1 conv.
    """

    route: str
    stride: int
    pad: int
    tau: int
    block: Optional[MatmulBlock]
    gemm: tuple
    vmem_bytes: int
    tile_rows: int = 0
    spatial_tiles: int = 1
    tile_cols: int = 0
    col_tiles: int = 1
    halo_mode: str = "none"
    halo: Optional[object] = None  # SpatialHalo (kept untyped: lazy import)

    @property
    def space_to_depth(self) -> bool:
        """Whether the kernel runs over the layer's space-to-depth input:
        the direct kernels read unit-stride windows only, so a strided
        direct conv does (:func:`repro.kernels.ops.space_to_depth_operands`)."""
        return self.route == "direct" and self.stride > 1


#: VMEM working-set model of one direct-conv grid step — lives with the rest
#: of the DSE scoring in core/dse.py; re-exported here because the engine is
#: its primary consumer (DESIGN.md §2).
_direct_conv_vmem = dse.direct_conv_vmem


def _resolve_pad(padding, kh: int) -> int:
    if isinstance(padding, int):
        return padding
    return {"SAME": kh // 2, "VALID": 0}[padding]


def validate_policy(config, policy: Optional[NumericsPolicy]) -> NumericsPolicy:
    """Check a numerics policy against a template config (DESIGN.md §8).

    A quantized policy only makes sense on the q16 backend (the float
    backends would silently run the QTensor raws as numbers); rejecting the
    combo here gives serve/scheduler callers one clear error instead of
    garbage logits.  Returns the resolved policy (float when ``None``).
    """
    policy = policy or NumericsPolicy("float")
    if policy.quantized and config.backend != "q16":
        raise ValueError(
            f"NumericsPolicy({policy.name!r}) requires the 'q16' backend, but "
            f"the template is configured with backend={config.backend!r}"
        )
    return policy


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class Engine:
    """Executes GEMM/conv plans for one template configuration.

    Stateless w.r.t. numerics; holds the (shared) plan cache and per-engine
    routing counters (``counters["conv_direct"]`` etc.) used by routing
    assertions in tests.
    """

    def __init__(self, config=None, plan_cache: Optional[PlanCache] = None) -> None:
        if config is None:
            from .template import TemplateConfig

            config = TemplateConfig()
        self.config = config
        # explicit `is not None`: an empty PlanCache is falsy (__len__ == 0)
        # but still the caller's requested isolated cache
        self.plan_cache = plan_cache if plan_cache is not None else plan_cache_for(config.hw)
        self.counters: collections.Counter = collections.Counter()
        # quantized-param cache: (id(params), policy) -> (params, qparams).
        # The strong ref to the source tree both prevents id-reuse aliasing
        # and documents the contract: weights are quantized exactly once per
        # (param tree, policy) per engine (DESIGN.md §8).
        self._qparam_cache: dict = {}
        self._calibrating = False
        self._act_maxabs = 0.0

    # -- planning ------------------------------------------------------------

    def block_for(self, m: int, n: int, k: int) -> MatmulBlock:
        """The Pallas block for a GEMM shape: config override or cached DSE."""
        if self.config.block is not None:
            return clamp_block(m, n, k, self.config.block, self.config.hw)
        return self.plan_cache.block_for(m, n, k, self.config.hw)

    @staticmethod
    def _active_mesh():
        from repro.parallel.sharding import active_mesh

        return active_mesh()

    def _adhoc_block(self, m: int, n: int, k: int) -> MatmulBlock:
        """Block for a *plan-less* GEMM dispatch: localize (m, n, k) under an
        active :func:`use_mesh` context first (ISSUE 9) — an ad-hoc call
        inside a mesh otherwise plans at the global shape, which
        ``plan_gemm(mesh=...)`` never executes, so a store warmed through
        the planner reports spurious misses for the very same layer."""
        mesh = self._active_mesh()
        if mesh is not None:
            from repro.parallel.sharding import local_gemm_shape

            m, n, k = local_gemm_shape(m, n, k, mesh=mesh)
        return self.block_for(m, n, k)

    def measure_and_pin(self, m: int, n: int, k: int, **kw) -> MatmulBlock:
        """Measured-time autotune for this engine's hardware spec — times the
        top-K analytic candidates and pins the winner in the registry."""
        return self.plan_cache.measure_and_pin(m, n, k, self.config.hw, **kw)

    def plan_gemm(
        self, m: int, n: int, k: int, *, mesh=None, partition=None
    ) -> GemmPlan:
        """Plan one GEMM; with ``mesh`` (+ optional PartitionSpec over
        (M, N[, K])) the *local per-shard* shape is planned instead of the
        logical one — a (16,16) mesh and a single chip produce different,
        each-correct, plans from the same registry (DESIGN.md §6)."""
        logical = ()
        if mesh is not None:
            from repro.parallel.sharding import local_gemm_shape

            lm, ln, lk = local_gemm_shape(m, n, k, mesh=mesh, partition=partition)
            if (lm, ln, lk) != (m, n, k):
                logical = (m, n, k)
            m, n, k = lm, ln, lk
        block = None if self.config.backend == "xla" else self.block_for(m, n, k)
        return GemmPlan(m=m, n=n, k=k, block=block, logical=logical)

    def plan_gemm_ladder(
        self, ladder: Sequence[int], n: int, k: int, *, batches: Sequence[int] = (1,),
        mesh=None, partition=None
    ) -> dict:
        """Plan one GEMM per (batch rung x bucket-ladder rung) product
        (M = batch * rung, fixed N/K).

        This is the scheduler's warmup primitive: planning every rung up
        front guarantees each bucket's shape is in the PlanRegistry before
        traffic arrives, so a mixed trace replayed against the warm registry
        (or a persisted store) reports ``misses == 0``.  ``batches`` extends
        the ladder to coalesced (B, L) prefill launches, whose GEMMs flatten
        the leading dims into M = B * L (:func:`batch_rungs`); the default
        (1,) is the plain per-rung ladder.
        """
        ms = sorted({int(b) * int(m) for b in batches for m in ladder})
        return {
            m: self.plan_gemm(m, n, k, mesh=mesh, partition=partition)
            for m in ms
        }

    def plan_conv(
        self, x_shape, w_shape, *, stride: int = 1, padding=0,
        route: Optional[str] = None, mesh=None, partition=None, spatial=None,
    ) -> ConvPlan:
        """Pick the kernel route for one conv layer (DESIGN.md §2).

        Direct route: the DSE (``dse.explore_conv_spatial``, memoized in the
        plan cache) picks the (τ, tile_rows, tile_cols, halo_mode)
        compute-unit config — whole-slab when the padded image fits the VMEM
        budget, otherwise a (𝒯, ℭ) spatial tiling whose halo regime the
        HBM-traffic score chooses (the manual-DMA regime wins over two-block
        whenever legal — strictly less re-streaming and residency).  Only
        when *no* config fits does the layer fall back to the im2col GEMM
        with a plan-cached DSE block.  ``route`` forces a route (tests /
        benchmarks).  With ``mesh`` the *local* shard of the layer is planned:
        batch over the partition's M axes, output channels over its N axes.

        ``spatial`` (a shard count, mesh axis name, or pre-chained
        :class:`SpatialHalo`) plans the cross-chip H-slab partition instead
        (DESIGN.md §10): the per-shard kernel runs at the halo-augmented
        ``win``-row window with padding folded into the exchange's zero fill,
        and the returned plan carries the seam in ``plan.halo`` — batch and
        Cout then stay shard-local, so ``partition`` does not apply.
        """
        if spatial is not None:
            from repro.parallel.sharding import (SpatialHalo,
                                                 plan_spatial_halo,
                                                 spatial_shards)

            n, h, wd, cin = x_shape
            kh = w_shape[0]
            pad = _resolve_pad(padding, kh)
            hs = spatial if isinstance(spatial, SpatialHalo) else plan_spatial_halo(
                h, kh, stride, pad, *spatial_shards(spatial, mesh)
            )
            inner = self.plan_conv(
                (n, hs.win, wd + 2 * pad, cin), w_shape,
                stride=stride, padding=0, route=route,
            )
            return dataclasses.replace(inner, halo=hs)
        if mesh is not None:
            from repro.parallel.sharding import local_conv_shapes

            x_shape, w_shape = local_conv_shapes(
                x_shape, w_shape, mesh=mesh, partition=partition
            )
        n, h, wd, cin = x_shape
        kh, kw, _, cout = w_shape
        pad = _resolve_pad(padding, kh)
        hp, wp = h + 2 * pad, wd + 2 * pad
        ho = (hp - kh) // stride + 1
        wo = (wp - kw) // stride + 1
        gemm = (n * ho * wo, cout, cin * kh * kw)
        backend = self.config.backend
        if backend == "xla" or route == "xla":
            return ConvPlan("xla", stride, pad, 0, None, gemm, 0)
        if route != "im2col":
            in_bytes = (self.config.qformat.total_bits // 8) if backend == "q16" else 4
            # planned as the stride-1 conv the kernel runs (the layer's
            # space-to-depth input when strided)
            choice = self.plan_cache.conv_tile_for(
                *space_to_depth_geometry(hp, wp, cin, kh, kw, stride),
                ho, wo, cout, in_bytes, self.config.hw,
            )
            if choice is not None:
                tile_rows = 0 if choice.tile_rows >= ho else choice.tile_rows
                tile_cols = 0 if (choice.tile_cols or wo) >= wo else choice.tile_cols
                halo_mode = choice.halo_mode or (
                    "two_block" if tile_rows else "none"
                )
                return ConvPlan(
                    "direct", stride, pad, choice.tau, None, gemm,
                    choice.vmem_bytes, tile_rows, choice.spatial_tiles,
                    tile_cols, choice.col_tiles, halo_mode,
                )
            if route == "direct":
                raise ValueError(
                    f"direct conv route forced but no (tau, tile_rows) config "
                    f"for image slab {x_shape} fits VMEM "
                    f"({self.config.hw.vmem_bytes} bytes)"
                )
        block = self.block_for(*gemm)
        return ConvPlan("im2col", stride, pad, 0, block, gemm, block.vmem_bytes())

    # -- fixed-point residency (the QTensor plane, DESIGN.md §8) -------------

    def quant(self, x, fmt: Optional[QFormat] = None) -> QTensor:
        """Float -> QTensor on the activation grid — a counted island *exit*.

        ``quantize_calls`` is the residency enforcement counter: between two
        consecutive grid-resident ops it must not tick, so a test tracing one
        q16 decode step can assert the count equals exactly the number of
        designated float islands (DESIGN.md §8).
        """
        if isinstance(x, QTensor):
            return x
        fmt = fmt or self.config.qformat
        self.counters["quantize_calls"] += 1
        if self._calibrating:
            # debug.callback so recording survives scan/jit tracing: the
            # concrete per-site max reaches the host at execution time
            jax.debug.callback(self._record_act_maxabs, jnp.max(jnp.abs(x)))
        return QTensor(quantize(x, fmt), fmt)

    def _record_act_maxabs(self, v) -> None:
        self._act_maxabs = max(self._act_maxabs, float(v))

    def calibrate_activation_format(self, run, *, total_bits: int = 16) -> QFormat:
        """The activation half of the max-abs calibration pass (DESIGN.md §8).

        Runs ``run()`` (an *eager* forward over a calibration batch) with
        every :meth:`quant` site recording the magnitude of the float value
        it is about to snap, then picks the smallest Qm.n whose range covers
        the observed maximum.  Per-tensor weight formats come from
        :meth:`quantize_weight`; activations share this one grid so every
        island exit lands on a single, kernel-static format.
        """
        from .quantization import calibrate_format

        self._act_maxabs = 0.0
        self._calibrating = True
        try:
            jax.block_until_ready(run())
            # block_until_ready waits on device buffers only; the host-side
            # recording callbacks need the effects barrier on async backends
            jax.effects_barrier()
        finally:
            self._calibrating = False
        return calibrate_format(
            jnp.float32(self._act_maxabs), total_bits=total_bits
        )

    def dequant(self, q, fmt: Optional[QFormat] = None, dtype=jnp.float32) -> jax.Array:
        """QTensor (or raw int16 + fmt) -> float — a counted island *entry*."""
        self.counters["dequantize_calls"] += 1
        if isinstance(q, QTensor):
            return dequantize(q.raw, q.fmt, dtype)
        return dequantize(q, fmt or self.config.qformat, dtype)

    def quantize_weight(
        self,
        w: jax.Array,
        policy: NumericsPolicy,
        fmt: Optional[QFormat] = None,
        contraction_axes: Optional[tuple] = None,
        fused_bias: bool = False,
        act_fmt: Optional[QFormat] = None,
        total_bits: Optional[int] = None,
    ) -> QTensor:
        """Quantize one persistent weight (calibrated per-tensor by default;
        ``fmt`` pins a format — e.g. biases stay on the activation grid so
        the accumulator alignment shift can never go negative).

        ``contraction_axes`` (the axes a GEMM/conv reduces over — (-2,) for
        dense (…, k, n) weights, the kh/kw/cin axes for conv) enables the
        *accumulator-headroom rule*: the int32 accumulator wraps (TPU-native;
        the FPGA DSP48 cascade is 48-bit, DESIGN.md §2), and the exact
        adversarial bound on one output is ``max|x_raw| · L1`` with L1 the
        largest per-output column sum of |w_raw|.  The calibrated fraction is
        capped so even ``max|x_raw| · L1`` cannot reach 2^31 — the finest
        weight grid that can never overflow, regardless of activation
        content; with ``fused_bias`` one extra headroom bit covers the
        in-kernel shifted bias add.  ``act_fmt`` names the activation grid
        feeding this layer (default ``policy.fmt``): an int8 input has
        ``max|x_raw| ≤ 2^7``, which widens the budget by 8 bits vs int16.
        ``total_bits`` pins the weight's *storage* rung (default: match the
        activation's — the int8 weight grid of the precision ladder).
        Counted separately from ``quantize_calls``: weight quantization
        happens once at preparation, never inside a step.
        """
        import math

        self.counters["weights_quantized"] += 1
        if fmt is not None:
            return quantize_qtensor(w, fmt)
        if not policy.per_tensor_weights:
            return quantize_qtensor(w, policy.fmt)
        act_fmt = act_fmt or policy.fmt
        total_bits = total_bits or act_fmt.total_bits
        max_frac = None
        if contraction_axes:
            l1 = float(jnp.max(jnp.sum(jnp.abs(w.astype(jnp.float32)),
                                       axis=contraction_axes)))
            if l1 > 0:
                # 2^(act_bits-1) * (L1 * 2^frac) < 2^31
                #   =>  frac <= 32 - act_bits - log2(L1)
                # (16/15 for int16 activations, 24/23 for int8), minus one
                # bit of margin when a bias add joins the epilogue
                budget = float(31 - (act_fmt.total_bits - 1) - (1 if fused_bias else 0))
                max_frac = math.floor(budget - math.log2(l1) - 1e-9)
        from .quantization import calibrate_format

        wfmt = calibrate_format(w, max_frac=max_frac, total_bits=total_bits)
        return QTensor(quantize(w, wfmt), wfmt)

    def qparams_for(self, params, policy: NumericsPolicy, build):
        """Quantize-once parameter cache, keyed by param-tree identity.

        ``build()`` constructs the quantized tree on the first call for a
        given (params, policy); later calls — a second `generate()`, every
        scheduler restart sharing the tree — return the cached tree without
        touching the weights (``qparam_cache_hits`` vs ``qparam_builds``).
        The cache holds a strong reference to the source tree, so an id()
        recycled by the allocator can never alias a different tree.
        """
        validate_policy(self.config, policy)
        key = (id(params), policy)
        ent = self._qparam_cache.get(key)
        if ent is not None and ent[0] is params:
            self.counters["qparam_cache_hits"] += 1
            return ent[1]
        self.counters["qparam_builds"] += 1
        qp = build()
        self._qparam_cache[key] = (params, qp)
        return qp

    def drop_qparams(self, params, policy: NumericsPolicy) -> bool:
        """Release one cached quantized tree (e.g. a calibration probe's —
        it was built under the provisional base policy and would otherwise
        pin a full int16 weight copy for the process lifetime)."""
        return self._qparam_cache.pop((id(params), policy), None) is not None

    def _quant_operand(self, v) -> QTensor:
        """QTensor passthrough; float operands are quantized inline (counted).

        Persistent weights should arrive pre-quantized via a qparam tree —
        the inline path exists so ad-hoc callers still compute correctly,
        at the cost of a visible ``quantize_calls`` tick per call.
        """
        if isinstance(v, QTensor):
            return v
        return self.quant(v)

    def _qbias_operand(self, bias, acc_frac: int):
        """Shared bias prep for the grid-resident GEMM/conv: quantize if
        needed and compute the accumulator alignment shift.  Returns
        (raw_or_None, bias_shift_or_None)."""
        if bias is None:
            return None, None
        bias = self._quant_operand(bias)
        bias_shift = acc_frac - bias.fmt.frac_bits
        if bias_shift < 0:
            raise ValueError(
                f"bias format {bias.fmt.name} is finer than the "
                f"2^-{acc_frac} accumulator grid"
            )
        return bias.raw, bias_shift

    def _qmatmul(
        self,
        x,
        w,
        *,
        bias=None,
        relu: bool = False,
        out_fmt: Optional[QFormat] = None,
        wide: bool = False,
        plan: Optional[GemmPlan] = None,
    ):
        """Grid-resident GEMM: QTensor in -> QTensor out, zero float hops.

        The requantize epilogue is fused into the kernel write-back (shift =
        fa + fb - fo); ``wide=True`` reads the int32 accumulator out instead
        and descales exactly — the final-logits island, counted as one
        dequantize.
        """
        from repro.kernels import ops as kops

        x = self._quant_operand(x)
        w = self._quant_operand(w)
        # stay on the *input's* activation grid by default: consecutive
        # grid-resident ops then agree on the format without the caller
        # re-stating the policy at every call site
        out_fmt = out_fmt or x.fmt
        lead = x.shape[:-1]
        k = x.shape[-1]
        n = w.shape[-1]
        x2 = x.reshape(-1, k)
        m = x2.shape[0]
        acc_frac = x.fmt.frac_bits + w.fmt.frac_bits
        b_raw, bias_shift = self._qbias_operand(bias, acc_frac)
        self.counters["gemm_q16"] += 1
        block = (
            plan.block
            if plan is not None and plan.block is not None
            else self._adhoc_block(m, n, k)
        )
        out = kops.matmul_q16(
            x2.raw, w.raw, bias=b_raw, relu=relu, fmt=out_fmt,
            shift=acc_frac - out_fmt.frac_bits, bias_shift=bias_shift,
            wide=wide, block=block, vmem_limit_bytes=self.config.hw.vmem_bytes,
        )
        if wide:
            self.counters["dequantize_calls"] += 1
            return (out.astype(jnp.float32) * 2.0 ** -acc_frac).reshape(*lead, n)
        return QTensor(out.reshape(*lead, n), out_fmt)

    def _qconv2d(
        self,
        x,
        w,
        *,
        stride: int = 1,
        padding=0,
        bias=None,
        relu: bool = False,
        out_fmt: Optional[QFormat] = None,
        plan: Optional[ConvPlan] = None,
    ) -> QTensor:
        """Grid-resident conv (direct or im2col route per the plan)."""
        from repro.kernels import ops as kops

        x = self._quant_operand(x)
        w = self._quant_operand(w)
        out_fmt = out_fmt or x.fmt  # same grid-following rule as _qmatmul
        if plan is not None and plan.halo is not None:
            return self._conv2d_spatial(
                x, w, bias=bias, relu=relu, qout=out_fmt, plan=plan
            )
        if plan is None:
            # ad-hoc dispatch inside use_mesh plans the *local* shard shape,
            # matching plan_conv(mesh=...) warmups (ISSUE 9)
            plan = self.plan_conv(
                x.shape, w.shape, stride=stride, padding=padding,
                mesh=self._active_mesh(),
            )
        if plan.route == "xla":
            raise ValueError("grid-resident conv has no xla route (q16 only)")
        stride, pad = plan.stride, plan.pad
        acc_frac = x.fmt.frac_bits + w.fmt.frac_bits
        b_raw, bias_shift = self._qbias_operand(bias, acc_frac)
        self.counters["conv_direct" if plan.route == "direct" else "conv_im2col"] += 1
        out = kops.conv2d_q16(
            x.raw, w.raw, bias=b_raw, stride=stride, padding=pad, tau=plan.tau,
            relu=relu, fmt=out_fmt, shift=acc_frac - out_fmt.frac_bits,
            bias_shift=bias_shift, route=plan.route, block=plan.block,
            tile_rows=plan.tile_rows, tile_cols=plan.tile_cols,
            halo_mode=plan.halo_mode, vmem_limit_bytes=self.config.hw.vmem_bytes,
        )
        return QTensor(out, out_fmt)

    # -- execution: GEMM -----------------------------------------------------

    def _xla_epilogue(self, out, bias, relu, qout, dtype):
        out = out.astype(dtype)
        if bias is not None:
            out = out + bias.astype(dtype)
        if relu:
            out = jax.nn.relu(out)
        if qout is not None:
            out = fake_quant_fmt(out, qout)  # STE: keeps the train path differentiable
        return out

    def matmul(
        self,
        x: jax.Array,
        w: jax.Array,
        *,
        bias: Optional[jax.Array] = None,
        relu: bool = False,
        qout: Optional[QFormat] = None,
        wide: bool = False,
        plan: Optional[GemmPlan] = None,
    ) -> jax.Array:
        """``x @ w`` with fused epilogue; leading dims of x flatten into M.

        On the q16 backend the output is inherently snapped to the backend's
        ``config.qformat`` grid by the kernel's saturating write-back, so
        ``qout`` is implied by the backend and ignored there (same rule as
        :meth:`conv2d`).

        QTensor operands take the *grid-resident* path (DESIGN.md §8): the
        GEMM consumes int16 raws, fuses the requantize epilogue in-kernel,
        and returns a QTensor — no float round-trip.  ``qout`` then names the
        output grid (default: the backend qformat) and ``wide=True`` returns
        exactly-descaled float logits from the int32 accumulator instead.
        """
        if isinstance(x, QTensor) or isinstance(w, QTensor):
            return self._qmatmul(
                x, w, bias=bias, relu=relu, out_fmt=qout, wide=wide, plan=plan
            )
        if x.ndim == 1:
            return self.matmul(x[None, :], w, bias=bias, relu=relu, qout=qout, plan=plan)[0]
        lead = x.shape[:-1]
        k = x.shape[-1]
        n = w.shape[-1]
        x2 = x.reshape(-1, k)
        m = x2.shape[0]
        backend = self.config.backend
        if backend == "xla":
            pet = self.config.accum_dtype or x.dtype
            out = jnp.dot(x2, w.astype(x.dtype), preferred_element_type=pet)
            out = self._xla_epilogue(out, bias, relu, qout, x.dtype)
        elif backend == "pallas":
            from repro.kernels import ops as kops

            self.counters["gemm_pallas"] += 1
            block = plan.block if plan is not None and plan.block is not None else self._adhoc_block(m, n, k)
            out = kops.matmul_fp(
                x2, w, bias=bias, relu=relu, qout=qout, block=block,
                vmem_limit_bytes=self.config.hw.vmem_bytes,
            )
        elif backend == "q16":
            from repro.kernels import ops as kops

            # legacy per-op fixed point: float operands are quantized and the
            # result dequantized *every call* — the counters make this float
            # round-trip visible so residency tests catch accidental use
            # (the stay-on-grid path is the QTensor dispatch above).
            self.counters["gemm_q16"] += 1
            self.counters["quantize_calls"] += 2 if bias is None else 3
            self.counters["dequantize_calls"] += 1
            fmt = self.config.qformat
            block = plan.block if plan is not None and plan.block is not None else self._adhoc_block(m, n, k)
            qres = kops.matmul_q16(
                quantize(x2, fmt),
                quantize(w, fmt),
                bias=None if bias is None else quantize(bias, fmt),
                relu=relu,
                fmt=fmt,
                block=block,
                vmem_limit_bytes=self.config.hw.vmem_bytes,
            )
            out = dequantize(qres, fmt, dtype=x.dtype)
        else:  # pragma: no cover - config validation
            raise ValueError(f"unknown backend {backend!r}")
        return out.reshape(*lead, n)

    def linear(
        self,
        x: jax.Array,
        w: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        relu: bool = False,
        qout: Optional[QFormat] = None,
        wide: bool = False,
        plan: Optional[GemmPlan] = None,
    ) -> jax.Array:
        return self.matmul(x, w, bias=b, relu=relu, qout=qout, wide=wide, plan=plan)

    # -- execution: conv -----------------------------------------------------

    def conv2d(
        self,
        x: jax.Array,
        w: jax.Array,
        *,
        stride: int = 1,
        padding=0,
        bias: Optional[jax.Array] = None,
        relu: bool = False,
        qout: Optional[QFormat] = None,
        plan: Optional[ConvPlan] = None,
    ) -> jax.Array:
        """NHWC conv through the planned kernel route, epilogue fused.

        x: (N, H, W, Cin), w: (K, K, Cin, Cout) -> (N, Ho, Wo, Cout).
        On the q16 backend the output is inherently Q-gridded, so ``qout``
        is implied by the backend's qformat.  QTensor operands take the
        grid-resident path and return a QTensor (DESIGN.md §8).
        """
        from repro.kernels import ops as kops

        if plan is not None and plan.halo is not None and not isinstance(x, QTensor):
            return self._conv2d_spatial(
                x, w, bias=bias, relu=relu, qout=qout, plan=plan
            )
        if isinstance(x, QTensor) or isinstance(w, QTensor):
            return self._qconv2d(
                x, w, stride=stride, padding=padding, bias=bias, relu=relu,
                out_fmt=qout, plan=plan,
            )
        kh, kw = w.shape[0], w.shape[1]
        if plan is None:
            # ad-hoc dispatch inside use_mesh plans the *local* shard shape,
            # matching plan_conv(mesh=...) warmups (ISSUE 9)
            plan = self.plan_conv(
                x.shape, w.shape, stride=stride, padding=padding,
                mesh=self._active_mesh(),
            )
        # The plan is the single source of geometry: stride *and* pad both
        # come from it, so a mismatched plan cannot half-apply.
        stride, pad = plan.stride, plan.pad
        backend = self.config.backend
        if plan.route == "xla":
            self.counters["conv_xla"] += 1
            xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0))) if pad else x
            cols, ho, wo = kops.im2col(xp, kh, kw, stride)
            pet = self.config.accum_dtype or x.dtype
            out = jnp.dot(cols, kops.conv_gemm_weights(w).astype(x.dtype),
                          preferred_element_type=pet)
            out = self._xla_epilogue(out, bias, relu, qout, x.dtype)
            return out.reshape(x.shape[0], ho, wo, -1)
        self.counters["conv_direct" if plan.route == "direct" else "conv_im2col"] += 1
        if backend == "pallas":
            return kops.conv2d(
                x, w, bias=bias, stride=stride, padding=pad, tau=plan.tau,
                relu=relu, qout=qout, route=plan.route, block=plan.block,
                tile_rows=plan.tile_rows, tile_cols=plan.tile_cols,
                halo_mode=plan.halo_mode, vmem_limit_bytes=self.config.hw.vmem_bytes,
            )
        assert backend == "q16", backend
        # legacy per-op fixed point (see matmul): quantize/dequantize every
        # call, counted so the float round-trip is visible.
        self.counters["quantize_calls"] += 2 if bias is None else 3
        self.counters["dequantize_calls"] += 1
        fmt = self.config.qformat
        qres = kops.conv2d_q16(
            quantize(x, fmt),
            quantize(w, fmt),
            bias=None if bias is None else quantize(bias, fmt),
            stride=stride,
            padding=pad,
            tau=plan.tau,
            relu=relu,
            fmt=fmt,
            route=plan.route,
            block=plan.block,
            tile_rows=plan.tile_rows,
            tile_cols=plan.tile_cols,
            halo_mode=plan.halo_mode,
            vmem_limit_bytes=self.config.hw.vmem_bytes,
        )
        return dequantize(qres, fmt, dtype=x.dtype)

    def _conv2d_spatial(self, x, w, *, bias, relu, qout, plan: ConvPlan):
        """One spatially-sharded conv seam (DESIGN.md §10).

        ``x`` is slab-major (S, N, lx, W, C) — float array or QTensor —
        with the slab dim (optionally) sharded over ``plan.halo.axis``.
        Exchange the halo rows with the neighbor shards, pre-pad W by the
        conv's ``pad`` (H zeros already came from the exchange's edge
        fill), run the planned per-shard kernel on each device's own slabs
        folded into the batch dim (:func:`~repro.parallel.sharding.map_slabs`),
        then restore the slab layout — masking the ragged tail
        shard's invalid rows back to zero so the *next* seam's halo reads
        stay exact.  Contraction dims never cross a shard boundary, so the
        result is bit-identical to the unsharded kernel per output row.
        """
        from repro.parallel import sharding as sh

        hs = plan.halo
        inner = dataclasses.replace(plan, halo=None)
        self.counters["conv_spatial"] += 1
        quant = isinstance(x, QTensor)
        v = x.raw if quant else x
        v = sh.constrain_slabs(v, hs.axis)
        ext = sh.halo_exchange(v, hs)  # (S, N, win, W, C)
        if hs.pad:
            ext = jnp.pad(
                ext, ((0, 0), (0, 0), (0, 0), (hs.pad, hs.pad), (0, 0))
            )
        def per_shard(e, w, bias):
            # this device's slabs, folded into the batch dim of the kernel
            s, n = e.shape[0], e.shape[1]
            flat = e.reshape(s * n, *e.shape[2:])
            out = self.conv2d(
                QTensor(flat, x.fmt) if quant else flat, w,
                bias=bias, relu=relu, qout=qout, plan=inner,
            )
            return out.reshape(s, n, *out.shape[1:])

        out = sh.map_slabs(per_shard, ext, w, bias, axis=hs.axis)
        qres = isinstance(out, QTensor)
        ov = sh.constrain_slabs(
            sh.mask_slab_rows(out.raw if qres else out, hs), hs.axis
        )
        return QTensor(ov, out.fmt) if qres else ov
