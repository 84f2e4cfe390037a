"""Slot-based continuous-batching serving engine.

A fixed pool of ``n_slots`` sequences shares one stacked decode cache; the
scheduler admits queued requests into free slots, finished sequences free
them.  The engine has exactly three jitted programs, all with static
shapes, so steady-state serving never retraces:

* **batched chunked prefill** — admitted prompts are padded onto a shared
  ``(n_slots, prefill_chunk)`` grid and every chunk is ONE ``T.forward``
  call.  A prompt of length L costs ``ceil(L / chunk)`` forward calls
  instead of L (the seed engine scanned one token at a time *and* retraced
  per prompt length).  Rows not being prefilled are masked out of the cache
  merge, so admission can overlap slots that are mid-decode.
* **decode step** — advances every active slot one token per call (the
  standard TPU serving shape), with per-slot positions so slots sit at
  different depths.
* **sampling** — temperature/top-k/top-p with per-slot PRNG keys
  (``serving.sampling``), one batched draw for prefill and decode alike.

With ``ServeConfig.quant_mode = "int4_packed"`` the engine calls
``quantize_for_serving`` once at build time: every large matmul weight is
stored as packed int4 nibbles and ``decode_step`` runs the paper's packed
matmul kernel straight off the stored nibbles — the serving-side payoff of
DSP-packing (decode is weight-bandwidth-bound).  ``int8``/``dsp_packed``
select the corresponding per-call arithmetic paths.

``quant_mode = "dsp_tuned"`` goes further: the ``repro.tuning`` planner
enumerates every legal packing plan for ``plan_bits`` — including
multi-DSP *column-packed* plans (``n_columns > 1``), which spread one dot
product across several packed int32 words and are the only legal plans for
``plan_bits=(8, 8)`` — scores each by simulated error, and picks per layer
the fastest plan whose MAE fits ``error_budget``; weights are quantized
once onto each layer's plan and decode runs per-layer pair-packed
arithmetic.  The chosen table is exposed as ``engine.plan_table`` (path →
``tuning.PlanReport``).

``quant_mode = "dsp_mixed"`` (or ``plan_bits="auto"``) adds the width axis
to that search: a sensitivity pass (``tuning.mixed``) measures, per
packable weight path, the logit damage of quantizing that layer alone at
each candidate ``(a_bits, w_bits)`` on seeded calibration activations,
and a greedy allocator assigns each layer its own width pair — narrow
widths (more packed multiplications per int32 word, cheaper plans) for
tolerant layers, wide plans for sensitive ones — under the model-level
``mixed_budget``.  The allocation is exposed as
``engine.mixed_allocation`` (a ``tuning.MixedAllocation``).

Termination goes through a single code path (``_finish_slot``): EOS,
per-request ``max_new`` and the cache-capacity bound all free the slot,
record the finish reason and report the rid to the caller.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from ..core.packed_params import (
    SERVING_MODES,
    fuse_projection_weights,
    quantize_for_serving,
)
from ..kernels.ops import on_tpu
from ..models import transformer as T
from ..models.config import ModelConfig
from .paged_cache import OutOfPages, PageAllocator
from .sampling import SamplingParams, sample_tokens, slot_key
from .scheduler import Scheduler

__all__ = ["ServeConfig", "Engine", "ContinuousEngine"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything the engines decide at build time, in one frozen record.

    The knobs split into capacity (``n_slots`` / ``max_len`` /
    ``prefill_chunk`` / the paged-cache group), arithmetic
    (``quant_mode`` and the plan-search group — these select which packed
    representation ``core.packed_params.quantize_for_serving`` builds),
    placement (``tp`` — tensor parallelism over the serving mesh,
    DESIGN.md §4) and policy (deadlines, the governor, default sampling).
    ``__post_init__`` rejects contradictory combinations at construction
    so an engine never has to re-validate; the two mutations it performs
    are promoting ``plan_bits="auto"`` + dsp_tuned to ``dsp_mixed``
    (per-layer width allocation IS the mixed mode) and resolving
    ``use_kernel=None`` from the backend.
    """

    n_slots: int = 8
    max_len: int = 512
    prefill_chunk: int = 16
    max_new: int = 64          # default per-request budget (submit can override)
    eos_token: int = 1
    # weight path: native | int8 | int4_packed | dsp_packed | dsp_tuned |
    # dsp_mixed (see core.packed_params.quantize_for_serving)
    quant_mode: str = "native"
    # compiled Pallas kernels vs the jnp references for the packed modes;
    # None resolves from the backend: kernels on a TPU at tp == 1 (no f32
    # shortcut grids are built there), references elsewhere
    use_kernel: bool | None = None
    # engine-build weight preprocessing for the packed decode fast path:
    # prepack builds device-resident packed operands once (words / zp rows /
    # f32-exact grids); fuse_projections concatenates same-input projections
    # so a decode step runs one GEMV where it ran several (bit-identical per
    # output column — quantization is per-channel).  "mlp" fuses up|gate,
    # "all" also fuses q|k|v.  Off by default: inside the scanned decode
    # step on CPU XLA the post-fusion splits cost more than the saved GEMV
    # dispatches (isolated layers DO win — this is a backend-specific call;
    # flip it on for TPU runs).
    prepack: bool = True
    fuse_projections: bool | str = "none"
    # dsp_tuned plan search: operand widths, MAE-per-extraction budget and
    # whether to wall-clock-autotune block sizes (off by default: the cost
    # proxy ranks identically and engine build stays fast).  plan_bits may
    # be the string "auto" instead of a width pair: widths are then chosen
    # PER LAYER by the sensitivity allocator (quant_mode "dsp_mixed" —
    # a dsp_tuned-mode config with plan_bits="auto" is promoted to it).
    plan_bits: tuple[int, int] | str = (4, 4)
    error_budget: float = 0.5
    autotune_plans: bool = False
    # dsp_mixed: the model-level error budget (total added mean logit-KL on
    # the calibration forward vs the uniform widest-candidate plan) the
    # greedy width allocator may spend, the candidate width pairs it
    # chooses from (None = tuning.mixed.DEFAULT_WIDTH_CANDIDATES), and the
    # calibration volume (tokens per sequence; seeded from ``seed``)
    mixed_budget: float = 0.05
    width_candidates: tuple[tuple[int, int], ...] | None = None
    calib_tokens: int = 32
    # paged KV cache (ContinuousEngine only; the fixed-slot Engine ignores
    # these).  page_size is the KV tokens per physical page; n_pages sizes
    # the shared pool (None = n_slots * ceil(grid / page_size) — memory
    # parity with the dense engine's per-slot windows); watermark_pages is
    # the free-page floor admission must not dip below (None = n_slots:
    # every decoding lane can grow one page before the pool runs dry)
    page_size: int = 16
    n_pages: int | None = None
    watermark_pages: int | None = None
    # persisted plan database (tuning.plandb): a Checkpointer directory the
    # build consults before running the dsp_tuned/dsp_mixed plan searches
    # and writes back to after a cold search, so restarted engines build in
    # seconds.  None = always search.  Keyed by plan_key(model, backend,
    # shapes, search settings) — anything that would change the search
    # result misses instead of serving stale plans.
    plan_db: str | None = None
    # per-request wall-clock deadline (milliseconds from submit).  A
    # request past its deadline is SHED — cancelled with finish_reason
    # "deadline" — at the next admission/step boundary instead of
    # occupying a lane; queued requests are shed without ever admitting.
    # None = no deadlines.
    deadline_ms: float | None = None
    # load-adaptive precision governor (serving.governor): hold prebuilt
    # degraded weight tiers and swap under load.  False = off; True =
    # default GovernorConfig; or a GovernorConfig instance.
    governor: Any = False
    # tensor-parallel degree: shard packed weights over ``tp`` devices'
    # "model" mesh axis (launch.mesh.make_serving_mesh) and run
    # the shard_map'd packed arithmetic (runtime.tp_packed) — decode is
    # bit-identical to tp=1 by construction.  Plan searches and the plan-
    # DB key are tp-aware: row-partitioned layers plan against the
    # widened (post-psum) packed word.  Only the jnp reference paths are
    # shard_map'd, so tp > 1 rejects use_kernel.
    tp: int = 1
    # default sampling (submit can override per request)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self) -> None:
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.n_pages is not None and self.n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {self.n_pages}")
        if self.watermark_pages is not None and self.watermark_pages < 0:
            raise ValueError(
                f"watermark_pages must be >= 0, got {self.watermark_pages}"
            )
        if self.quant_mode not in SERVING_MODES:
            raise ValueError(
                f"quant_mode {self.quant_mode!r} not in {SERVING_MODES}"
            )
        if self.fuse_projections not in (True, False, "none", "mlp", "all"):
            raise ValueError(
                f"fuse_projections {self.fuse_projections!r} not in "
                "(True, False, 'none', 'mlp', 'all')"
            )
        if self.plan_bits == "auto":
            # "auto" means per-layer width allocation — that IS dsp_mixed
            if self.quant_mode == "dsp_tuned":
                object.__setattr__(self, "quant_mode", "dsp_mixed")
            elif self.quant_mode != "dsp_mixed":
                raise ValueError(
                    'plan_bits="auto" needs quant_mode "dsp_tuned" or '
                    f'"dsp_mixed", got {self.quant_mode!r}'
                )
        elif isinstance(self.plan_bits, str):
            raise ValueError(
                f"plan_bits {self.plan_bits!r} must be a (a_bits, w_bits) "
                'pair or "auto"'
            )
        if self.mixed_budget < 0:
            raise ValueError(
                f"mixed_budget must be >= 0, got {self.mixed_budget}"
            )
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {self.deadline_ms}"
            )
        if self.governor and self.quant_mode not in ("dsp_tuned", "dsp_mixed"):
            # governor tiers are per-layer DspTunedLeaf plan tables; the
            # other modes have no plan machinery to re-tier through
            raise ValueError(
                "governor needs quant_mode dsp_tuned or dsp_mixed, got "
                f"{self.quant_mode!r}"
            )
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.use_kernel is None:
            object.__setattr__(self, "use_kernel", self.tp == 1 and on_tpu())
        if self.tp > 1 and self.use_kernel:
            raise ValueError(
                "tp > 1 runs the shard_map'd jnp reference paths; "
                "use_kernel=True is not supported under tensor parallelism"
            )
        if self.quant_mode == "dsp_mixed" and self.autotune_plans:
            # the width allocator selects plans by cost proxy only; a
            # silent no-op here would let the flag lie about what ran
            raise ValueError(
                "autotune_plans is not supported with dsp_mixed: per-layer "
                "width allocation ranks plans by the cost proxy (use "
                "dsp_tuned for wall-clock block sweeps)"
            )


def _prepare_serving_params(cfg: ModelConfig, params, serve_cfg: ServeConfig,
                            mixed_allocation=None):
    """Engine-build weight preparation shared by ``Engine`` and
    ``ContinuousEngine``: switch the arithmetic mode, optionally fuse
    same-input projections, run the dsp_tuned/dsp_mixed plan searches and
    quantize the weights onto the chosen plans.

    Returns ``(cfg, params, plan_table, mixed_allocation, float_params,
    plan_db_stats)`` where ``float_params`` is the post-fusion float tree
    the quantized ``params`` were built from — the governor builds its
    degraded weight tiers from it so every tier's leaf paths line up with
    the primary's — and ``plan_db_stats`` records the DB consultation
    (hits/misses/stale + the key), or None when no DB was configured.

    When ``serve_cfg.plan_db`` names a plan-database directory, the
    dsp_tuned/dsp_mixed plan searches consult it first (keyed by
    ``tuning.plan_key`` over the post-fusion tree — the tree actually
    quantized) and fall back to search-and-store on a miss, so a warm
    build runs no measurement at all.  A caller-supplied
    ``mixed_allocation`` bypasses the DB in both directions: it is served
    as given and never written back (its paths may not match this key).
    """
    plan_table: dict = {}
    resolved_mixed = None
    float_params = params
    db = db_key = None
    if mixed_allocation is not None and serve_cfg.quant_mode != "dsp_mixed":
        # dropping a caller-measured allocation would silently serve
        # different plans than the caller benchmarked
        raise ValueError(
            "mixed_allocation was given but quant_mode is "
            f"{serve_cfg.quant_mode!r}; it is only served under "
            '"dsp_mixed"'
        )
    if serve_cfg.quant_mode not in ("native", "none"):
        # switch the arithmetic mode but preserve the caller's other
        # LinearSpec choices (dsp_spec correction scheme, act_bits).
        # dsp_mixed leaves route through the dsp_tuned arithmetic —
        # each DspTunedLeaf carries its own (per-layer) plan.
        linear_mode = (
            "dsp_tuned" if serve_cfg.quant_mode == "dsp_mixed"
            else serve_cfg.quant_mode
        )
        cfg = dataclasses.replace(
            cfg,
            quant=dataclasses.replace(
                cfg.quant, mode=linear_mode,
                use_kernel=serve_cfg.use_kernel,
            ),
        )
        fuse = serve_cfg.fuse_projections
        if fuse not in (False, "none"):
            # fused same-input GEMVs — bit-identical per output column
            # under per-channel quantization
            # (core.packed_params.fuse_projection_weights)
            params = fuse_projection_weights(
                params, fuse_attn=fuse in (True, "all"), fuse_mlp=True
            )
        float_params = params  # post-fusion, pre-quantization
        if (serve_cfg.plan_db
                and serve_cfg.quant_mode in ("dsp_tuned", "dsp_mixed")):
            from ..tuning.plandb import PlanDB, plan_key

            db = PlanDB(serve_cfg.plan_db)
            db_key = plan_key(cfg, serve_cfg, params)
        if serve_cfg.quant_mode == "dsp_mixed":
            if mixed_allocation is None and db is not None:
                entry = db.get(db_key)
                if entry is not None and entry.get("kind") == "mixed":
                    from ..tuning.plandb import allocation_from_json

                    mixed_allocation = allocation_from_json(
                        entry["allocation"]
                    )
            if mixed_allocation is None:
                from ..tuning.mixed import (
                    DEFAULT_WIDTH_CANDIDATES,
                    mixed_precision_plan,
                )

                # sensitivity pass + greedy width allocation on
                # calibration activations (tuning.mixed): per-layer
                # (a_bits, w_bits) under the model-level mixed_budget;
                # the per-width plan search keeps plans provably exact
                # so the only error the model sees is the quantization
                # the pass measured
                mixed_allocation = mixed_precision_plan(
                    params, cfg,
                    mixed_budget=serve_cfg.mixed_budget,
                    widths=(serve_cfg.width_candidates
                            or DEFAULT_WIDTH_CANDIDATES),
                    n_calib_tokens=serve_cfg.calib_tokens,
                    seed=serve_cfg.seed,
                    exact_first=not serve_cfg.use_kernel,
                    shard_groups=serve_cfg.tp,
                )
                if db is not None:
                    from ..tuning.plandb import allocation_to_json

                    db.put(db_key, {
                        "kind": "mixed",
                        "allocation": allocation_to_json(mixed_allocation),
                    })
            resolved_mixed = mixed_allocation
            plan_table = mixed_allocation.plans
            params = quantize_for_serving(
                params, "dsp_mixed", plans=plan_table,
                prepack=serve_cfg.prepack,
            )
        elif serve_cfg.quant_mode == "dsp_tuned":
            plan_table = None
            if db is not None:
                entry = db.get(db_key)
                if entry is not None and entry.get("kind") == "tuned":
                    from ..tuning.plandb import report_from_json

                    plan_table = {
                        p: report_from_json(r)
                        for p, r in entry["plans"].items()
                    }
            if plan_table is None:
                from ..tuning import plan_linear_layers

                a_bits, w_bits = serve_cfg.plan_bits
                plan_table = plan_linear_layers(
                    params, a_bits=a_bits, w_bits=w_bits,
                    error_budget=serve_cfg.error_budget,
                    autotune=serve_cfg.autotune_plans,
                    # non-kernel serving runs proven-exact plans through
                    # the f32-GEMM shortcut — rank those first (see
                    # rank_plans)
                    exact_first=not serve_cfg.use_kernel,
                    # row-partitioned layers plan against the widened
                    # (post-psum) packed word (see tuner.rank_plans)
                    shard_groups=serve_cfg.tp,
                )
                if db is not None:
                    from ..tuning.plandb import report_to_json

                    db.put(db_key, {
                        "kind": "tuned",
                        "plans": {p: report_to_json(r)
                                  for p, r in plan_table.items()},
                    })
            params = quantize_for_serving(
                params, "dsp_tuned", plans=plan_table,
                prepack=serve_cfg.prepack,
            )
        else:
            params = quantize_for_serving(
                params, serve_cfg.quant_mode, prepack=serve_cfg.prepack
            )
    db_stats = None if db is None else {
        "directory": db.directory, "key": db_key,
        "hits": db.n_hits, "misses": db.n_misses, "stale": db.n_stale,
    }
    return cfg, params, plan_table, resolved_mixed, float_params, db_stats


def _shard_for_tp(params, serve_cfg: ServeConfig, devices=None):
    """Mesh-partition a quantized serving tree when ``serve_cfg.tp > 1``,
    over ``devices`` (default: the first ``tp``).

    Returns ``(mesh, params)`` — ``(None, params)`` untouched at tp=1.
    The wrap happens AFTER quantization (the packed operands are what
    shards) and raises the certificate-clause-citing error for a row
    sharding whose widened accumulation would overflow
    (``runtime.tp_packed.shard_params_tp``)."""
    if serve_cfg.tp <= 1:
        return None, params
    from ..launch.mesh import make_serving_mesh
    from ..runtime.tp_packed import shard_params_tp

    mesh = make_serving_mesh(serve_cfg.tp, devices)
    return mesh, shard_params_tp(
        params, mesh, use_kernel=serve_cfg.use_kernel
    )


def _setup_governor(engine, cfg, float_params, serve_cfg) -> None:
    """Attach the load-adaptive precision governor (shared by both
    engines): build the tier ladder from the post-fusion float weights
    and hold it prequantized, ready to swap at a step boundary.

    When a plan database is configured, the tier ladders' plan tables are
    persisted under the engine's ``plan_key`` entry (``"tiers"`` record,
    fingerprinted by the governor knobs that shape them) so a warm
    governed build runs ZERO tier plan searches — the PR-9 follow-up.
    Weight payloads are never persisted; quantization always re-runs."""
    engine.governor = None
    engine.tiers = None
    engine.active_tier = 0
    if not serve_cfg.governor:
        return
    from .governor import Governor, GovernorConfig, build_tiers

    gcfg = (serve_cfg.governor
            if isinstance(serve_cfg.governor, GovernorConfig)
            else GovernorConfig())
    # consult the plan DB for persisted tier ladders; the fingerprint pins
    # every knob the tier searches read, so a changed ladder shape misses
    # instead of serving the wrong tiers
    fingerprint = {
        "narrow_bits": list(gcfg.narrow_bits),
        "emergency_tier": gcfg.emergency_tier,
        "emergency_max_mae": gcfg.emergency_max_mae,
        "use_kernel": serve_cfg.use_kernel,
    }
    db = entry = tables = None
    if serve_cfg.plan_db and engine.plan_db_stats:
        from ..tuning.plandb import PlanDB, report_from_json

        db = PlanDB(serve_cfg.plan_db)
        entry = db.get(engine.plan_db_stats["key"])
        stored = (entry or {}).get("tiers")
        if stored and stored.get("fingerprint") == fingerprint:
            tables = {
                name: {p: report_from_json(r) for p, r in tbl.items()}
                for name, tbl in stored["tables"].items()
            }
    engine.tiers = build_tiers(
        cfg, float_params, serve_cfg, engine.params, engine.plan_table, gcfg,
        tables=tables, shard_groups=serve_cfg.tp,
    )
    if db is not None and tables is None:
        # merge-write the fresh ladders next to the plan entry (never
        # clobber the "kind"/"plans" record _prepare_serving_params wrote)
        from ..tuning.plandb import report_to_json

        payload = dict(entry or {})
        payload["tiers"] = {
            "fingerprint": fingerprint,
            "tables": {
                t.name: {p: report_to_json(r)
                         for p, r in t.plan_table.items()}
                for t in engine.tiers if t.name != "primary"
            },
        }
        db.put(engine.plan_db_stats["key"], payload)
    if getattr(engine, "mesh", None) is not None:
        # non-primary tiers were quantized single-device: partition them
        # onto the engine's mesh so a swap stays a pointer repoint
        from ..runtime.tp_packed import shard_params_tp

        engine.tiers = tuple(
            t if t.params is engine.params else dataclasses.replace(
                t, params=shard_params_tp(
                    t.params, engine.mesh, use_kernel=serve_cfg.use_kernel
                )
            )
            for t in engine.tiers
        )
    engine.governor = Governor(gcfg, len(engine.tiers))


def _place(engine, devices) -> None:
    """Commit an engine's weights, precision tiers and cache to its devices;
    the jitted steps then run there.  Under tensor parallelism the leaves
    the mesh did not partition are replicated over it (instead of being
    re-sent from one device every step); at tp=1 everything goes to
    ``devices[0]`` (a replica's own chip), or stays uncommitted on the
    default device without ``devices``."""
    if engine.mesh is None:
        if not devices:
            return
        put = partial(jax.device_put, device=devices[0])
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(engine.mesh, PartitionSpec())

        def on_mesh(a):
            return (isinstance(a.sharding, NamedSharding)
                    and a.sharding.mesh == engine.mesh)

        def put(tree):
            return jax.tree.map(
                lambda a: a if on_mesh(a) else jax.device_put(a, rep), tree
            )
    engine.cache = put(engine.cache)
    if engine.tiers is not None:
        engine.tiers = tuple(
            dataclasses.replace(t, params=put(t.params)) for t in engine.tiers
        )
        engine.params = engine.tiers[engine.active_tier].params
    else:
        engine.params = put(engine.params)


class Engine:
    """Fixed-slot batched serving engine (DESIGN.md §3).

    Each admitted request owns one of ``n_slots`` lanes and that lane's
    dense cache window for its whole lifetime; capacity is a slot count,
    nothing is paged or preempted.  The request lifecycle:

    * :meth:`submit` queues a prompt (returns its rid; ``admit=True``
      pulls it into a free slot immediately);
    * :meth:`step` advances the whole batch one phase — shed expired
      deadlines, let the governor re-tier, admit into free slots, then
      either prefill one chunk (while any slot is still prefilling) or
      decode one token per active slot — and returns the rids finished
      this step;
    * finished tokens are read back via :attr:`outputs` /
      :meth:`drain_stream`, counters via :meth:`stats`;
    * :meth:`cancel` aborts a queued or running request with a
      ``CANCEL_REASONS`` finish reason (its slot frees at the next step
      boundary); :meth:`generate` wraps the loop for batch callers.

    The quantization mode never changes this surface: every
    ``quant_mode`` (and every tensor-parallel degree — the weights are
    sharded at build by ``runtime.tp_packed``) serves bit-identical
    tokens through the same step loop, which is what lets the
    conformance suites drive all modes through one engine API.
    """

    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig,
                 mixed_allocation=None, devices=None):
        """``mixed_allocation`` (a ``tuning.MixedAllocation``) skips the
        dsp_mixed engine-build sensitivity pass and serves the given
        per-layer plan table instead — for callers that already measured
        (the serving benchmark probes budgets before building).  Its paths
        must match this engine's param tree (same fusion settings).
        ``devices`` (``serve_cfg.tp`` of them) pins the engine to a device
        slice — ``serving.replica`` gives each replica its own; default:
        the first ``tp`` devices, uncommitted at tp=1."""
        (cfg, params, self.plan_table, self.mixed_allocation, float_params,
         self.plan_db_stats) = _prepare_serving_params(
            cfg, params, serve_cfg, mixed_allocation
        )
        self.mesh, params = _shard_for_tp(params, serve_cfg, devices)
        self.cfg = cfg
        self.params = params
        self.scfg = serve_cfg
        _setup_governor(self, cfg, float_params, serve_cfg)
        b = serve_cfg.n_slots
        # Chunked prefill needs contiguous cache writes, and a ring-buffer
        # (sliding-window) cache only supports single-position writes — a
        # chunk landing in the ring would overwrite slots that earlier
        # in-chunk queries still need — so sliding windows keep chunk=1.
        # Recurrent families (ssm/hybrid) prefill in full chunks: the
        # forward's ``valid`` mask advances each row's state by exactly its
        # own prompt tokens, and the mixers' masked scan re-applies the
        # single-token chunk math so a chunk of C tokens is bit-identical
        # to C single-token calls (the invariant — see ``models.ssm``).
        self._chunk = 1 if cfg.sliding_window else max(
            1, min(serve_cfg.prefill_chunk, serve_cfg.max_len)
        )
        # the prefill grid is padded to whole chunks, so allocate the cache
        # on the same grid — otherwise the last chunk's writes would clamp
        # at max_len and shift K/V backwards over earlier positions
        window = -(-serve_cfg.max_len // self._chunk) * self._chunk
        self.cache = T.init_cache(cfg, b, window)
        # per-leaf batch axis: attention KV leaves carry the slot axis at 1,
        # stacked recurrent state (mlstm/mamba) at 2 — locate it by shape
        # difference between a b-slot and a (b+1)-slot cache
        s_b = jax.eval_shape(lambda: T.init_cache(cfg, b, window))
        s_b1 = jax.eval_shape(lambda: T.init_cache(cfg, b + 1, window))
        self._batch_axes = jax.tree.map(
            lambda x, y: next(
                i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q
            ),
            s_b, s_b1,
        )
        _place(self, devices)
        self.positions = np.zeros(b, np.int32)
        self.active = np.zeros(b, bool)
        self.last_token = np.zeros(b, np.int32)
        self._slot_rid = np.full(b, -1, np.int64)
        # per-slot sampling state (set at admission from the request)
        self._temperature = np.zeros(b, np.float32)
        self._top_k = np.zeros(b, np.int32)
        self._top_p = np.ones(b, np.float32)
        self._keys = np.zeros((b, 2), np.uint32)
        self._base_key = jax.random.PRNGKey(serve_cfg.seed)
        self.scheduler = Scheduler()
        self._stream: deque[tuple[int, int]] = deque()
        self._sample = jax.jit(sample_tokens)
        # Device-resident decode state: steady-state decode advances tokens/
        # positions ON DEVICE and only syncs the sampled token back, so a
        # step does ONE host->device transfer worth of dispatch instead of
        # seven (~0.5 ms/step of device_put on CPU).  The numpy arrays above
        # stay authoritative for scheduling logic; ``_dev_dirty`` marks host
        # -side mutations (admission, finishes) that must be re-pushed.
        self._dev_state = None
        self._dev_dirty = True

    # ---- jitted steps ---------------------------------------------------
    @staticmethod
    def _row_select(mask, leaf, axis):
        """Broadcast a (n_slots,) bool mask against ``leaf`` along its
        batch ``axis``."""
        shape = [1] * leaf.ndim
        shape[axis] = mask.shape[0]
        return mask.reshape(shape)

    @partial(jax.jit, static_argnums=(0,))
    def _reset_slots(self, cache, row_mask):
        """Zero the cache state of the slots in ``row_mask`` — a freshly
        admitted request must not continue from the previous occupant's
        recurrent state or stale KV."""
        return jax.tree.map(
            lambda leaf, ax: jnp.where(
                self._row_select(row_mask, leaf, ax),
                jnp.zeros((), leaf.dtype), leaf,
            ),
            cache, self._batch_axes,
        )

    @partial(jax.jit, static_argnums=(0,))
    def _prefill_chunk(self, params, cache, tokens, base, row_mask, last_idx,
                       last_hidden):
        """One chunk of batched prefill.

        ``tokens``: (n_slots, C) — rows selected by ``row_mask`` carry
        prompt tokens for positions ``[base, base + C)``; other rows are
        ignored (their cache updates are masked out of the merge).
        Collects each admitted row's last-prompt-token *hidden state* into
        ``last_hidden`` when that position falls inside this chunk; the
        lm_head runs once on the gathered rows (``_lm_head``), not on every
        position of every chunk.
        """
        b, c = tokens.shape
        positions = jnp.broadcast_to(base + jnp.arange(c)[None], (b, c))
        # per-row prefix mask: recurrent state advances only over each
        # row's real prompt tokens; MoE capacity ignores everything else
        valid = row_mask[:, None] & (positions <= last_idx[:, None])
        hidden, new_cache, _ = T.forward(
            params, self.cfg, tokens, positions=positions, cache=cache,
            return_hidden=True, valid=valid,
        )
        cache = jax.tree.map(
            lambda old, new, ax: jnp.where(
                self._row_select(row_mask, old, ax), new, old
            ),
            cache, new_cache, self._batch_axes,
        )
        idx = jnp.clip(last_idx - base, 0, c - 1)
        row_hidden = jnp.take_along_axis(
            hidden, idx[:, None, None], axis=1
        )[:, 0]
        in_chunk = row_mask & (last_idx >= base) & (last_idx < base + c)
        last_hidden = jnp.where(
            in_chunk[:, None], row_hidden.astype(last_hidden.dtype), last_hidden
        )
        return cache, last_hidden

    @partial(jax.jit, static_argnums=(0,))
    def _lm_head(self, params, hidden):
        """(n_slots, d) hidden → (n_slots, V) f32 logits (mirrors
        ``T.forward``'s head)."""
        if self.cfg.tie_embeddings:
            return hidden.astype(jnp.float32) @ params["embed"]["w"].T.astype(
                jnp.float32
            )
        from ..core.packed_linear import apply_linear

        return apply_linear(
            params["lm_head"], hidden, self.cfg.quant
        ).astype(jnp.float32)

    def _push_state(self) -> None:
        """Host → device refresh of the decode state (admission/finish)."""
        self._dev_state = jax.device_put({
            "tokens": self.last_token,
            "positions": self.positions,
            "active": self.active,
            "keys": self._keys,
            "temperature": self._temperature,
            "top_k": self._top_k,
            "top_p": self._top_p,
        })
        self._dev_dirty = False

    @partial(jax.jit, static_argnums=(0,))
    def _decode_step(self, params, cache, state):
        """One decode step off the device-resident state; tokens/positions
        advance on device (active rows only — mirroring the host loop), so
        steady-state decode does no host→device transfers at all."""
        tokens, positions = state["tokens"], state["positions"]
        active = state["active"]
        # valid=active: inactive rows neither advance recurrent state nor
        # compete for MoE expert capacity
        logits, new_cache, _ = T.forward(
            params, self.cfg, tokens[:, None], positions=positions[:, None],
            cache=cache, valid=active[:, None],
        )
        nxt = sample_tokens(
            logits[:, -1], state["keys"], positions, state["temperature"],
            state["top_k"], state["top_p"],
        )
        new_state = dict(
            state,
            tokens=jnp.where(active, nxt, tokens),
            positions=positions + active.astype(positions.dtype),
        )
        return new_cache, new_state, nxt

    # ---- request lifecycle ----------------------------------------------
    def submit(self, prompt: list[int], max_new: int | None = None,
               sampling: SamplingParams | None = None,
               admit: bool = True, deadline_ms: float | None = None) -> int:
        """Enqueue a request; it is admitted as soon as a slot frees up.

        ``admit=False`` defers admission to the next ``step()`` so that a
        burst of submissions shares one batched prefill pass.
        ``deadline_ms`` overrides the engine-wide ``ServeConfig.deadline_ms``
        for this request (wall-clock budget from submission; a request
        still unfinished past it is shed with finish_reason "deadline").
        Returns the request id (outputs appear in ``outputs[rid]``).
        """
        # exact capacity bound: the cache holds max_len token positions (its
        # chunk-padded window is >= max_len), a prompt of exactly max_len
        # fills them all and still yields one sampled token before the
        # ``positions >= max_len`` termination fires — so only longer
        # prompts are impossible
        if len(prompt) > self.scfg.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} > max_len ({self.scfg.max_len})"
            )
        if max_new is None:
            max_new = self.scfg.max_new
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if sampling is None:
            sampling = SamplingParams(
                self.scfg.temperature, self.scfg.top_k, self.scfg.top_p
            )
        if deadline_ms is None:
            deadline_ms = self.scfg.deadline_ms
        rid = self.scheduler.submit(
            prompt, max_new, sampling,
            deadline_s=None if deadline_ms is None else deadline_ms / 1e3,
        )
        if admit:
            self._admit()
        return rid

    def _admit(self) -> list[int]:
        """Move queued requests into free slots: batched chunked prefill +
        first-token sample.  Returns rids finished during admission (a
        first token can already hit EOS or a 1-token budget)."""
        free = np.flatnonzero(~self.active)
        admitted = self.scheduler.admit(len(free))
        if not admitted:
            return []
        t0 = time.monotonic()
        b, c = self.scfg.n_slots, self._chunk
        lmax = max(len(r.prompt) for r in admitted)
        n_chunks = -(-lmax // c)
        tokens = np.zeros((b, n_chunks * c), np.int32)
        row_mask = np.zeros(b, bool)
        last_idx = np.zeros(b, np.int32)
        for slot, req in zip(free, admitted):
            ln = len(req.prompt)
            tokens[slot, :ln] = req.prompt
            row_mask[slot] = True
            last_idx[slot] = ln - 1
            self.positions[slot] = ln
            self.active[slot] = True
            self._slot_rid[slot] = req.rid
            self._temperature[slot] = req.sampling.temperature
            self._top_k[slot] = req.sampling.top_k
            self._top_p[slot] = req.sampling.top_p
            self._keys[slot] = np.asarray(slot_key(self._base_key, req.rid))

        cache = self._reset_slots(self.cache, jnp.asarray(row_mask))
        last_hidden = jnp.zeros((b, self.cfg.d_model), T._dtype(self.cfg))
        last_idx_j = jnp.asarray(last_idx)
        for ci in range(n_chunks):
            base = ci * c
            # rows whose prompt is already fully written skip later chunks
            mask_c = jnp.asarray(row_mask & (last_idx >= base))
            cache, last_hidden = self._prefill_chunk(
                self.params, cache,
                jnp.asarray(tokens[:, base:base + c]), jnp.int32(base),
                mask_c, last_idx_j, last_hidden,
            )
            # TTFT is per request: stamp each request when ITS last chunk
            # lands, not when the whole mixed batch drains — otherwise a
            # 4-token prompt admitted next to a 500-token one is charged
            # the long prompt's chunk time.  The sync makes the stamp
            # honest (dispatch alone would timestamp unfinished work).
            own_done = [r for r in admitted if (len(r.prompt) - 1) // c == ci]
            if own_done:
                jax.block_until_ready(cache)
                self.scheduler.note_prefill_done(own_done)
        self.cache = cache

        first = np.asarray(self._sample(
            self._lm_head(self.params, last_hidden),
            jnp.asarray(self._keys), last_idx_j,
            jnp.asarray(self._temperature), jnp.asarray(self._top_k),
            jnp.asarray(self._top_p),
        ))
        n_prompt_tokens = sum(len(r.prompt) for r in admitted)
        self.scheduler.note_prefill(n_prompt_tokens, time.monotonic() - t0,
                                    rows=n_chunks * b * c)
        finished = []
        for slot, req in zip(free, admitted):
            tok = int(first[slot])
            req.tokens.append(tok)
            self._stream.append((req.rid, tok))
            self.last_token[slot] = tok
            rid = self._maybe_finish(slot, tok)
            if rid is not None:
                finished.append(rid)
        self._dev_dirty = True  # admission rewrote slot state on the host
        return finished

    def _maybe_finish(self, slot: int, tok: int) -> int | None:
        """Single termination path: EOS, per-request budget and cache
        capacity all land here."""
        req = self.scheduler.requests[int(self._slot_rid[slot])]
        if tok == self.scfg.eos_token:
            return self._finish_slot(slot, "eos")
        if len(req.tokens) >= req.max_new:
            return self._finish_slot(slot, "length")
        # positions[slot] is the next cache write index; decode at position
        # max_len or beyond would write outside the max_len contract, so
        # the last admissible decode reads position max_len - 1
        if self.positions[slot] >= self.scfg.max_len:
            return self._finish_slot(slot, "length")
        return None

    def _finish_slot(self, slot: int, reason: str) -> int:
        rid = int(self._slot_rid[slot])
        self.active[slot] = False
        self._slot_rid[slot] = -1
        self.scheduler.finish(rid, reason)
        return rid

    def _release_rid(self, rid: int) -> None:
        """Free the slot of a cancelled *running* request (scheduler
        accounting already done by ``Scheduler.cancel``)."""
        for slot in np.flatnonzero(self._slot_rid == rid):
            self.active[slot] = False
            self._slot_rid[slot] = -1
            self._dev_dirty = True

    def cancel(self, rid: int, reason: str = "cancelled") -> None:
        """Abort an unfinished request immediately: a queued rid is
        dequeued without admission, a running rid's slot frees for the
        next admission.  Emitted tokens stay in ``outputs[rid]``."""
        if not self.scheduler.cancel(rid, reason):
            self._release_rid(rid)

    def _shed_expired(self) -> list[int]:
        """Cancel every deadline-expired request (finish_reason
        "deadline") — queued ones never occupy a slot, running ones free
        theirs at this step boundary."""
        shed = []
        for rid in self.scheduler.expired():
            if not self.scheduler.cancel(rid, "deadline"):
                self._release_rid(rid)
            shed.append(rid)
        return shed

    def set_tier(self, tier: int) -> None:
        """Swap the active precision tier at a step boundary.  Weights and
        plan table repoint; KV cache, positions and sampling state are
        untouched — the jitted steps specialize per plan table, so the
        next step simply runs the other arithmetic."""
        if self.tiers is None:
            raise RuntimeError(
                "engine was built without a governor (ServeConfig.governor)"
            )
        if not 0 <= tier < len(self.tiers):
            raise ValueError(
                f"tier {tier} out of range [0, {len(self.tiers)})"
            )
        if tier == self.active_tier:
            return
        t = self.tiers[tier]
        self.params = t.params
        self.plan_table = t.plan_table
        self.active_tier = tier

    def _govern(self, slow_step_ms: float | None = None) -> None:
        if self.governor is None:
            return
        target = self.governor.observe(
            self.scheduler.n_queued, slow_step_ms=slow_step_ms
        )
        if target != self.active_tier:
            self.set_tier(target)

    def step(self) -> list[int]:
        """Shed expired requests, let the governor re-tier, admit what
        fits, then advance every active slot one token.  Returns the rids
        that finished this step."""
        self._shed_expired()
        self._govern()
        finished = self._admit()
        if not self.active.any():
            return finished
        t0 = time.monotonic()
        if self._dev_dirty:
            self._push_state()
        self.cache, self._dev_state, nxt = self._decode_step(
            self.params, self.cache, self._dev_state
        )
        nxt = np.asarray(nxt)
        active_slots = np.flatnonzero(self.active)
        self.scheduler.note_decode(len(active_slots), time.monotonic() - t0,
                                   rows=self.scfg.n_slots)
        n_finished = len(finished)
        for slot in active_slots:
            # numpy mirrors advance exactly like the device state did
            self.positions[slot] += 1
            tok = int(nxt[slot])
            rid_s = int(self._slot_rid[slot])
            self.scheduler.requests[rid_s].tokens.append(tok)
            self._stream.append((rid_s, tok))
            self.last_token[slot] = tok
            rid = self._maybe_finish(slot, tok)
            if rid is not None:
                finished.append(rid)
        if len(finished) > n_finished:
            self._dev_dirty = True  # freed slots changed the active mask
        return finished

    def generate(self, prompts: list[list[int]], max_new: int | None = None,
                 sampling: SamplingParams | None = None) -> dict[int, list[int]]:
        """Drive a batch of prompts to completion (reference loop)."""
        rids = [self.submit(p, max_new=max_new, sampling=sampling, admit=False)
                for p in prompts]
        per_req = max_new if max_new is not None else self.scfg.max_new
        budget = per_req * len(prompts) + len(prompts) + 1
        for _ in range(budget):
            if not (self.active.any() or self.scheduler.n_queued):
                break
            self.step()
        assert not (self.active.any() or self.scheduler.n_queued), \
            "generate() exceeded its step budget"
        return {r: list(self.scheduler.requests[r].tokens) for r in rids}

    # ---- introspection --------------------------------------------------
    def drain_stream(self) -> list[tuple[int, int]]:
        """Pop every ``(rid, token)`` emitted since the last drain, in
        emission order — the streaming-output hook for callers that relay
        tokens as they land instead of waiting for the request to finish."""
        out = list(self._stream)
        self._stream.clear()
        return out

    @property
    def outputs(self) -> dict[int, list[int]]:
        """rid -> tokens emitted so far, for every request that produced
        any (finished or not); cancelled requests keep what they emitted
        before the cancel."""
        return {r.rid: r.tokens for r in self.scheduler.requests.values()
                if r.tokens}

    def peek_logits(self) -> np.ndarray:
        """(n_slots, V) next-token logits for the current state, without
        advancing it — used by the packed-vs-float tolerance tests."""
        return np.asarray(self._peek(
            self.params, self.cache, jnp.asarray(self.last_token),
            jnp.asarray(self.positions),
        ))

    @partial(jax.jit, static_argnums=(0,))
    def _peek(self, params, cache, tokens, positions):
        logits, _, _ = T.forward(
            params, self.cfg, tokens[:, None], positions=positions[:, None],
            cache=cache,
        )
        return logits[:, -1].astype(jnp.float32)

    def stats(self) -> dict:
        """Scheduler counters (queue depth, per-phase tok/s, TTFT/latency
        percentiles) plus, when attached, the governor's swap history and
        active tier name and the plan database's hit/miss counts."""
        s = self.scheduler.stats()
        if self.governor is not None:
            s["governor"] = dict(
                self.governor.stats(),
                tier_name=self.tiers[self.active_tier].name,
            )
        if self.plan_db_stats is not None:
            s["plan_db"] = dict(self.plan_db_stats)
        return s


class ContinuousEngine:
    """Continuous-batching engine over a paged KV cache.

    Where ``Engine`` pins a request to a slot-sized dense cache window for
    its whole lifetime, this engine decouples *lanes* (rows of the batched
    forward, ``n_slots`` of them) from *memory* (a shared pool of
    ``n_pages`` fixed-size KV pages, ``serving.paged_cache``).  The three
    consequences the traffic bench measures:

    * **continuous admission** — a request is admitted the moment a lane
      AND its pages are free; it prefills one chunk per engine step
      alongside the lanes that are already decoding, and joins the decode
      batch the step after its own last chunk lands.  Short requests no
      longer queue behind a long request that is merely *decoding*.
    * **memory by need, not by worst case** — a request holds
      ``ceil(len/page_size)`` pages for its actual length, growing one
      page per ``page_size`` decode steps; admission is gated by a
      free-page ``watermark`` instead of a slot count.  When decode growth
      still runs dry the youngest request is preempted (pages freed,
      requeued at the *front*); the (rid, position)-keyed sampler makes
      the resume bit-identical to the uninterrupted stream.
    * **prefix sharing** — ``register_shared_prefix`` marks a common
      system prompt; its pages are prefilled once and adopted by every
      later request that starts with it (refcounted, copy-on-write when a
      write lands in a shared page).

    Token-identity contract: for the same single-request workload this
    engine emits exactly the tokens ``Engine`` emits, in every quant mode
    — the paged attention branch masks to the same valid positions and
    the sampler draws from the same (rid, position) streams.

    Every registry family serves here.  Recurrent state (ssm / hybrid
    mamba) is O(1) per lane, so it lives as per-lane arrays beside the KV
    pools (``init_paged_cache(batch=...)``); the forward's ``valid`` mask
    keeps each lane's state advancing only over its own real tokens, and
    because the mixers' masked scan is bit-identical to single-token calls
    (``models.ssm``), chunked prefill — and the re-prefill that resumes a
    preempted request — reproduce the uninterrupted state exactly.
    Sliding-window KV pages the ring buffer (chunk-1 prefill, as in
    ``Engine``); a pure-ssm model needs no pages at all.
    """

    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig,
                 mixed_allocation=None, devices=None):
        """Arguments as for :class:`Engine`."""
        with TraceAnnotation("serve.build.pack"):
            (cfg, params, self.plan_table, self.mixed_allocation,
             float_params, self.plan_db_stats) = _prepare_serving_params(
                cfg, params, serve_cfg, mixed_allocation
            )
        self.mesh, params = _shard_for_tp(params, serve_cfg, devices)
        self.cfg = cfg
        self.params = params
        self.scfg = serve_cfg
        _setup_governor(self, cfg, float_params, serve_cfg)
        # step wall times (``step``) feed the governor's slow-step signal
        # (rolling median over the retained window); recorded whether or
        # not a governor is attached — stats() surfaces the median either
        # way (runtime.fault_tolerance.StragglerDetector, host id 0)
        from ..runtime.fault_tolerance import StragglerDetector

        self.straggler = StragglerDetector(
            window=(self.governor.config.window
                    if self.governor is not None else 16)
        )
        b = serve_cfg.n_slots
        # sliding windows keep chunk-1 prefill (ring writes are single-
        # position); recurrent families chunk via the ``valid`` mask
        self._chunk = 1 if cfg.sliding_window else max(
            1, min(serve_cfg.prefill_chunk, serve_cfg.max_len)
        )
        # the per-lane logical window is the chunk-padded grid, exactly like
        # the dense engine's cache window — identical attention windows are
        # what make the two engines token-identical
        grid = -(-serve_cfg.max_len // self._chunk) * self._chunk
        ps = serve_cfg.page_size
        if cfg.family == "ssm":
            # pure recurrent state: O(1) per lane, nothing to page
            self._max_blocks = 0
        elif cfg.sliding_window:
            # ring pages: a lane never addresses more than the window
            self._max_blocks = -(-min(grid, cfg.sliding_window) // ps)
        else:
            self._max_blocks = -(-grid // ps)
        n_pages = (serve_cfg.n_pages if serve_cfg.n_pages is not None
                   else max(1, b * self._max_blocks))
        if n_pages < self._max_blocks:
            raise ValueError(
                f"n_pages={n_pages} cannot hold one max-length request "
                f"({self._max_blocks} blocks of {ps})"
            )
        wm = (serve_cfg.watermark_pages
              if serve_cfg.watermark_pages is not None else b)
        self.alloc = PageAllocator(n_pages, ps, min(wm, n_pages - 1))
        with TraceAnnotation("serve.build.kv_pool"):
            self.cache = T.init_paged_cache(cfg, n_pages, ps, batch=b)
        with TraceAnnotation("serve.build.place"):
            _place(self, devices)
        # Per-leaf lane axis: recurrent-state leaves carry the lane (batch)
        # axis, page-pool leaves don't — locate it by shape difference
        # between a b-lane and a (b+1)-lane cache, sentinel -1 for pool
        # leaves.  Drives admission state resets and restricts CoW page
        # copies to pool leaves.
        s_b = jax.eval_shape(
            lambda: T.init_paged_cache(cfg, n_pages, ps, batch=b)
        )
        s_b1 = jax.eval_shape(
            lambda: T.init_paged_cache(cfg, n_pages, ps, batch=b + 1)
        )
        self._lane_axes = jax.tree.map(
            lambda x, y: next(
                (i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                 if p != q),
                -1,
            ),
            s_b, s_b1,
        )
        self._has_state = cfg.family in ("ssm", "hybrid")
        # host lane state (authoritative for scheduling; mirrored on device
        # for the decode loop, _push_state)
        self.positions = np.zeros(b, np.int32)   # next cache write index
        self.active = np.zeros(b, bool)          # lane holds a request
        self._prefilling = np.zeros(b, bool)     # ...still prefilling it
        self._n_seq = np.zeros(b, np.int32)      # tokens to prefill
        self._last_idx = np.zeros(b, np.int32)   # n_seq - 1
        self.last_token = np.zeros(b, np.int32)
        self._lane_rid = np.full(b, -1, np.int64)
        self._seq: dict[int, np.ndarray] = {}    # lane -> prefill tokens
        self._temperature = np.zeros(b, np.float32)
        self._top_k = np.zeros(b, np.int32)
        self._top_p = np.ones(b, np.float32)
        self._keys = np.zeros((b, 2), np.uint32)
        self._base_key = jax.random.PRNGKey(serve_cfg.seed)
        self._last_hidden = jnp.zeros((b, cfg.d_model), T._dtype(cfg))
        self.scheduler = Scheduler()
        self._stream: deque[tuple[int, int]] = deque()
        self._sample = jax.jit(sample_tokens)
        self._dev_state = None
        self._dev_dirty = True
        self._pushed_mask = None  # decode mask the device state was built for
        # shared system-prompt prefix (register_shared_prefix)
        self._shared_prefix: list[int] | None = None
        self._shared_key: tuple | None = None
        self._shared_ready = False
        self._shared_pending_rid = -1
        # the step clock (``step``): when this step's last device wait
        # returned, and (start, prefill, decode) of dispatched work that
        # no wait has covered yet
        self._t_synced: float | None = None
        self._unsynced: tuple[float, bool, bool] | None = None

    # ---- jitted steps ---------------------------------------------------
    @partial(jax.jit, static_argnums=(0,))
    def _reset_lanes(self, cache, lane_mask):
        """Zero the recurrent-state leaves of the lanes in ``lane_mask`` —
        a freshly admitted request must not continue from the previous
        occupant's state.  Pool leaves (lane axis -1) are untouched: page
        recycling already isolates them."""
        return jax.tree.map(
            lambda leaf, ax: leaf if ax < 0 else jnp.where(
                Engine._row_select(lane_mask, leaf, ax),
                jnp.zeros((), leaf.dtype), leaf,
            ),
            cache, self._lane_axes,
        )

    @partial(jax.jit, static_argnums=(0,))
    def _prefill_chunk(self, params, cache, tokens, base, page_table,
                       row_mask, last_idx, last_hidden):
        """One prefill chunk for every prefilling lane at once; lanes sit
        at *different* depths (per-row ``base``).  Rows outside
        ``row_mask`` get the invalid page sentinel, so their KV writes
        drop, and ``valid`` is False across their whole chunk, so the
        masked recurrent scan returns their state bit-unchanged — no cache
        merge pass needed (unlike the dense engine)."""
        b, c = tokens.shape
        positions = base[:, None] + jnp.arange(c)[None]
        pt_eff = jnp.where(row_mask[:, None], page_table, self.alloc.invalid)
        valid = row_mask[:, None] & (positions <= last_idx[:, None])
        hidden, new_cache, _ = T.forward(
            params, self.cfg, tokens, positions=positions, cache=cache,
            return_hidden=True, page_table=pt_eff, valid=valid,
        )
        idx = jnp.clip(last_idx - base, 0, c - 1)
        row_hidden = jnp.take_along_axis(
            hidden, idx[:, None, None], axis=1
        )[:, 0]
        in_chunk = row_mask & (last_idx >= base) & (last_idx < base + c)
        last_hidden = jnp.where(
            in_chunk[:, None], row_hidden.astype(last_hidden.dtype),
            last_hidden,
        )
        return new_cache, last_hidden

    @partial(jax.jit, static_argnums=(0,))
    def _lm_head(self, params, hidden):
        if self.cfg.tie_embeddings:
            return hidden.astype(jnp.float32) @ params["embed"]["w"].T.astype(
                jnp.float32
            )
        from ..core.packed_linear import apply_linear

        return apply_linear(
            params["lm_head"], hidden, self.cfg.quant
        ).astype(jnp.float32)

    @partial(jax.jit, static_argnums=(0,))
    def _decode_step(self, params, cache, state):
        """Advance every decoding lane one token (device-resident state,
        as in ``Engine``); non-decoding lanes get the invalid page
        sentinel so their KV writes drop, and ``valid=active`` so their
        recurrent state stays bit-unchanged (a still-prefilling lane must
        not advance on a junk decode token)."""
        tokens, positions = state["tokens"], state["positions"]
        active = state["active"]
        pt_eff = jnp.where(
            active[:, None], state["page_table"], self.alloc.invalid
        )
        logits, new_cache, _ = T.forward(
            params, self.cfg, tokens[:, None], positions=positions[:, None],
            cache=cache, page_table=pt_eff, valid=active[:, None],
        )
        nxt = sample_tokens(
            logits[:, -1], state["keys"], positions, state["temperature"],
            state["top_k"], state["top_p"],
        )
        new_state = dict(
            state,
            tokens=jnp.where(active, nxt, tokens),
            positions=positions + active.astype(positions.dtype),
        )
        return new_cache, new_state, nxt

    @partial(jax.jit, static_argnums=(0,))
    def _copy_page(self, cache, src, dst):
        """Copy-on-write device copy: physical page ``src`` -> ``dst``
        across every layer's K and V pool (recurrent-state leaves have no
        pages — untouched)."""
        return jax.tree.map(
            lambda leaf, ax: (
                leaf if ax >= 0 else leaf.at[:, dst].set(leaf[:, src])
            ),
            cache, self._lane_axes,
        )

    @partial(annotate_function, name="serve.decode.push")
    def _push_state(self, decode_mask) -> None:
        self._dev_state = jax.device_put({
            "tokens": self.last_token,
            "positions": self.positions,
            "active": decode_mask,
            "keys": self._keys,
            "temperature": self._temperature,
            "top_k": self._top_k,
            "top_p": self._top_p,
            "page_table": self.alloc.table_array(
                self._lane_rid, self._max_blocks
            ),
        })
        self._pushed_mask = np.asarray(decode_mask).copy()
        self._dev_dirty = False

    # ---- shared prefix ---------------------------------------------------
    def register_shared_prefix(self, tokens: list[int]) -> None:
        """Declare a common system prompt.  The first admitted request
        that starts with it prefills it once; every later request that
        starts with it adopts those pages (refcounted, CoW on write)
        and prefills only its own suffix."""
        # Shared prefixes are the one feature that still excludes some
        # families — each guard names the exact blocking feature.
        if self.cfg.family == "ssm":
            raise ValueError(
                f"register_shared_prefix: unsupported for {self.cfg.name!r} "
                "(family 'ssm'); blocking feature: recurrent state — the "
                "prefix's decode state is a per-lane array, not shareable "
                "KV pages"
            )
        if self.cfg.family == "hybrid":
            raise ValueError(
                f"register_shared_prefix: unsupported for {self.cfg.name!r} "
                "(family 'hybrid'); blocking feature: mamba recurrent "
                "state — shared KV pages capture only the attention "
                "layers' prefix state, so an adopting request would resume "
                "from a zero mamba state"
            )
        if self.cfg.sliding_window:
            raise ValueError(
                f"register_shared_prefix: unsupported for {self.cfg.name!r}"
                f"; blocking feature: sliding_window={self.cfg.sliding_window}"
                " — ring slots are position-ambiguous across requests "
                "(slot = pos % window), so prefix pages cannot be adopted"
            )
        if self._shared_prefix is not None:
            raise ValueError("shared prefix already registered")
        if not tokens:
            raise ValueError("empty shared prefix")
        if self.scheduler.requests:
            raise ValueError(
                "register the shared prefix before submitting requests"
            )
        blocks = self.alloc.blocks_for(len(tokens))
        if self.alloc.n_pages < self._max_blocks + blocks:
            raise ValueError(
                f"n_pages={self.alloc.n_pages} cannot pin a {blocks}-block "
                f"shared prefix and still hold one max-length request "
                f"({self._max_blocks} blocks)"
            )
        self._shared_prefix = list(tokens)
        self._shared_key = ("prefix", tuple(tokens))

    def _matches_prefix(self, prompt: list[int]) -> bool:
        sp = self._shared_prefix
        return (sp is not None and len(prompt) >= len(sp)
                and list(prompt[: len(sp)]) == sp)

    # ---- request lifecycle ----------------------------------------------
    def submit(self, prompt: list[int], max_new: int | None = None,
               sampling: SamplingParams | None = None,
               admit: bool = True, deadline_ms: float | None = None) -> int:
        """Enqueue a request (same contract as ``Engine.submit``); it is
        admitted as soon as a lane and its pages are free."""
        if len(prompt) > self.scfg.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} > max_len ({self.scfg.max_len})"
            )
        if max_new is None:
            max_new = self.scfg.max_new
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if sampling is None:
            sampling = SamplingParams(
                self.scfg.temperature, self.scfg.top_k, self.scfg.top_p
            )
        if deadline_ms is None:
            deadline_ms = self.scfg.deadline_ms
        with TraceAnnotation("serve.submit", rid=self.scheduler.next_rid):
            rid = self.scheduler.submit(
                prompt, max_new, sampling,
                deadline_s=None if deadline_ms is None else deadline_ms / 1e3,
            )
        if admit:
            self._admit_new()
        return rid

    def _admission_plan(self, req) -> tuple[list[int], int, int, int]:
        """(seq, start, blocks_total, pages_needed) for admitting ``req``.

        ``seq`` is prompt + already-emitted tokens (a preempted request
        re-prefills its own output; the position-keyed sampler then
        resumes the identical stream).  ``start`` skips adopted shared-
        prefix tokens.  ``pages_needed`` counts fresh pages: the blocks
        beyond the adopted ones, plus one CoW page when the first written
        block is shared."""
        seq = list(req.prompt) + list(req.tokens)
        n_seq = len(seq)
        start = 0
        adopted_blocks = 0
        if self._shared_ready and self._matches_prefix(seq):
            # token-granular resume: re-prefill at least the last token so
            # there is a hidden state to sample from (prompt == prefix)
            start = min(len(self._shared_prefix), n_seq - 1)
            adopted_blocks = self.alloc.shared_blocks(self._shared_key)
        n_chunks = -(-(n_seq - start) // self._chunk)
        padded_end = start + n_chunks * self._chunk
        blocks_total = min(
            self.alloc.blocks_for(padded_end), self._max_blocks
        )
        need = max(0, blocks_total - adopted_blocks)
        if adopted_blocks and start // self.alloc.page_size < adopted_blocks:
            need += 1  # CoW of the partial shared page the prefill writes
        return seq, start, blocks_total, need

    @partial(annotate_function, name="serve.admit")
    def _admit_new(self) -> None:
        """Admit queued requests into free lanes, strictly FIFO: if the
        front request's pages would dip the free list below the watermark,
        nobody skips ahead of it.  An idle engine ignores the watermark —
        it exists to protect running lanes, and there are none."""
        while True:
            free = np.flatnonzero(~self.active)
            if len(free) == 0:
                break
            req = self.scheduler.peek()
            if req is None:
                break
            seq, start, blocks_total, need = self._admission_plan(req)
            if not (self.alloc.can_admit(need)
                    or (not self.active.any() and need <= self.alloc.n_free)):
                break
            req = self.scheduler.admit_front()
            with TraceAnnotation("serve.admit.request", rid=req.rid):
                lane = int(free[0])
                self.alloc.open_table(req.rid)
                adopting = start > 0
                if adopting:
                    self.alloc.adopt_shared(self._shared_key, req.rid)
                self.alloc.grow(req.rid, blocks_total)
                # CoW every block the prefill will write into (only a shared
                # partial page ever actually copies)
                for blk in range(start // self.alloc.page_size, blocks_total):
                    page, src = self.alloc.make_writable(req.rid, blk)
                    if src is not None:
                        self.cache = self._copy_page(
                            self.cache, jnp.int32(src), jnp.int32(page)
                        )
                if self._has_state:
                    # the new occupant must start from zero recurrent state
                    lane_mask = np.zeros(self.scfg.n_slots, bool)
                    lane_mask[lane] = True
                    self.cache = self._reset_lanes(
                        self.cache, jnp.asarray(lane_mask)
                    )
                self._lane_rid[lane] = req.rid
                self.active[lane] = True
                self._prefilling[lane] = True
                self._seq[lane] = np.asarray(seq, np.int32)
                self._n_seq[lane] = len(seq)
                self._last_idx[lane] = len(seq) - 1
                self.positions[lane] = start
                self._temperature[lane] = req.sampling.temperature
                self._top_k[lane] = req.sampling.top_k
                self._top_p[lane] = req.sampling.top_p
                self._keys[lane] = np.asarray(slot_key(self._base_key, req.rid))
                if (self._shared_prefix is not None and not self._shared_ready
                        and self._shared_pending_rid < 0
                        and self._matches_prefix(req.prompt)):
                    # first matching request prefills the prefix for everyone;
                    # its pages are pinned once its prefill completes
                    self._shared_pending_rid = req.rid
                self._dev_dirty = True

    @partial(annotate_function, name="serve.prefill")
    def _prefill_step(self) -> list[int]:
        """One chunk of prefill for every prefilling lane.  Lanes whose
        last chunk landed sample their first token, get their TTFT stamp,
        and join the decode batch next step."""
        lanes = np.flatnonzero(self._prefilling)
        if len(lanes) == 0:
            return []
        b, c = self.scfg.n_slots, self._chunk
        with TraceAnnotation("serve.prefill.inputs"):
            tokens = np.zeros((b, c), np.int32)
            base = np.zeros(b, np.int32)
            row_mask = np.zeros(b, bool)
            n_tok = 0
            for lane in lanes:
                pos = int(self.positions[lane])
                chunk = self._seq[lane][pos:pos + c]
                tokens[lane, : len(chunk)] = chunk
                base[lane] = pos
                row_mask[lane] = True
                n_tok += len(chunk)
            inputs = (
                jnp.asarray(tokens), jnp.asarray(base),
                jnp.asarray(self.alloc.table_array(
                    self._lane_rid, self._max_blocks
                )),
                jnp.asarray(row_mask), jnp.asarray(self._last_idx),
            )
        with TraceAnnotation("serve.prefill.dispatch"):
            self.cache, self._last_hidden = self._prefill_chunk(
                self.params, self.cache, *inputs, self._last_hidden,
            )
        self.scheduler.note_prefill(n_tok, rows=tokens.size)
        done_lanes = []
        for lane in lanes:
            if self.positions[lane] + c >= self._n_seq[lane]:
                self.positions[lane] = self._n_seq[lane]
                done_lanes.append(int(lane))
            else:
                self.positions[lane] += c
        finished: list[int] = []
        if done_lanes:
            # honest per-request TTFT: sync before stamping, and sample the
            # completed lanes' first tokens right now
            with TraceAnnotation("serve.first_token"):
                first = np.asarray(self._sample(
                    self._lm_head(self.params, self._last_hidden),
                    jnp.asarray(self._keys), jnp.asarray(self._last_idx),
                    jnp.asarray(self._temperature), jnp.asarray(self._top_k),
                    jnp.asarray(self._top_p),
                ))
            self._t_synced = time.monotonic()
            done_reqs = []
            for lane in done_lanes:
                rid = int(self._lane_rid[lane])
                req = self.scheduler.requests[rid]
                done_reqs.append(req)
                self._prefilling[lane] = False
                self._seq.pop(lane, None)
                tok = int(first[lane])
                req.tokens.append(tok)
                self._stream.append((rid, tok))
                self.last_token[lane] = tok
                if rid == self._shared_pending_rid:
                    # prefix pages now hold real KV — pin and publish them
                    self.alloc.register_shared(
                        self._shared_key, rid,
                        self.alloc.blocks_for(len(self._shared_prefix)),
                    )
                    self._shared_ready = True
                    self._shared_pending_rid = -1
                fin = self._maybe_finish(lane, tok)
                if fin is not None:
                    finished.append(fin)
            self.scheduler.note_prefill_done(done_reqs)
        # no _dev_dirty here: prefill touches no decode-lane state — lanes
        # that just completed join the decode mask next step, and that
        # membership change itself forces the state push (_decode_once)
        return finished

    def _youngest_lane(self, exclude: int | None = None) -> int | None:
        """Lane holding the newest request (preemption victim)."""
        best, best_rid = None, -1
        for lane in np.flatnonzero(self.active):
            if exclude is not None and int(lane) == exclude:
                continue
            rid = int(self._lane_rid[lane])
            if rid > best_rid:
                best, best_rid = int(lane), rid
        return best

    def _preempt(self, lane: int) -> None:
        """Evict a lane: free its pages and requeue it at the FRONT of
        the queue.  Its emitted tokens are kept — re-admission re-prefills
        prompt+tokens and the (rid, position)-keyed sampler continues the
        identical stream."""
        rid = int(self._lane_rid[lane])
        if rid == self._shared_pending_rid:
            self._shared_pending_rid = -1  # its prefix pages never landed
        self.alloc.free(rid)
        self.scheduler.requeue(rid)
        self.active[lane] = False
        self._prefilling[lane] = False
        self._lane_rid[lane] = -1
        self._seq.pop(lane, None)
        self._dev_dirty = True

    @partial(annotate_function, name="serve.decode.pages")
    def _ensure_decode_pages(self, lanes: list[int]) -> None:
        """Grow each decoding lane's table to cover its next write; on
        ``OutOfPages`` preempt the youngest active request and retry
        (oldest lanes are served first, so pressure evicts the newest)."""
        for lane in sorted(lanes, key=lambda l: int(self._lane_rid[l])):
            if not self.active[lane]:
                continue  # preempted by an earlier lane's growth
            rid = int(self._lane_rid[lane])
            # a lane never addresses more than _max_blocks blocks: the ring
            # (sliding window) wraps, and a pure-ssm lane has no pages
            needed = min(
                int(self.positions[lane]) // self.alloc.page_size + 1,
                self._max_blocks,
            )
            if needed == 0:
                continue
            while True:
                try:
                    if self.alloc.grow(rid, needed):
                        # the device page table must see the new block or
                        # this step's KV write silently drops
                        self._dev_dirty = True
                    break
                except OutOfPages:
                    victim = self._youngest_lane()
                    if victim is None or (victim == lane
                                          and self.active.sum() <= 1):
                        raise  # one lone request outgrew the pool: config
                    self._preempt(victim)
                    if victim == lane:
                        break
            if not self.active[lane]:
                continue
            if self.cfg.sliding_window:
                # the next write lands at ring slot pos % window
                wblk = (
                    int(self.positions[lane]) % self.cfg.sliding_window
                ) // self.alloc.page_size
            else:
                wblk = needed - 1
            page, src = self.alloc.make_writable(rid, wblk)
            if src is not None:
                self.cache = self._copy_page(
                    self.cache, jnp.int32(src), jnp.int32(page)
                )
                self._dev_dirty = True

    @partial(annotate_function, name="serve.decode")
    def _decode_once(self, decode_mask: np.ndarray) -> list[int]:
        """Advance the decode batch one token (lanes in ``decode_mask``
        that are still active — preemption may have evicted some)."""
        lanes = np.flatnonzero(decode_mask & self.active)
        if len(lanes) == 0:
            return []
        self._ensure_decode_pages([int(l) for l in lanes])
        lanes = np.flatnonzero(decode_mask & self.active)
        if len(lanes) == 0:
            return []
        # push on explicit dirt OR a decode-membership change: a lane that
        # finished its prefill in a step whose push preceded it (the decode
        # batch is snapshotted before the prefill phase) would otherwise be
        # frozen out of the cached device mask and decode garbage
        mask = decode_mask & self.active
        if (self._dev_dirty or self._pushed_mask is None
                or not np.array_equal(mask, self._pushed_mask)):
            self._push_state(mask)
        with TraceAnnotation("serve.decode.dispatch"):
            self.cache, self._dev_state, nxt = self._decode_step(
                self.params, self.cache, self._dev_state
            )
        with TraceAnnotation("serve.decode.wait"):
            nxt = np.asarray(nxt)
        self._t_synced = time.monotonic()
        self.scheduler.note_decode(len(lanes), rows=mask.size)
        finished = []
        with TraceAnnotation("serve.decode.emit"):
            for lane in lanes:
                self.positions[lane] += 1
                tok = int(nxt[lane])
                rid = int(self._lane_rid[lane])
                self.scheduler.requests[rid].tokens.append(tok)
                self._stream.append((rid, tok))
                self.last_token[lane] = tok
                fin = self._maybe_finish(int(lane), tok)
                if fin is not None:
                    finished.append(fin)
        if finished:
            self._dev_dirty = True
        return finished

    def _maybe_finish(self, lane: int, tok: int) -> int | None:
        """Single termination path (EOS / budget / capacity), mirroring
        ``Engine._maybe_finish`` exactly — same bounds, same reasons."""
        rid = int(self._lane_rid[lane])
        req = self.scheduler.requests[rid]
        if tok == self.scfg.eos_token:
            return self._finish_lane(lane, "eos")
        if len(req.tokens) >= req.max_new:
            return self._finish_lane(lane, "length")
        if self.positions[lane] >= self.scfg.max_len:
            return self._finish_lane(lane, "length")
        return None

    def _finish_lane(self, lane: int, reason: str) -> int:
        rid = int(self._lane_rid[lane])
        with TraceAnnotation("serve.finish", rid=rid):
            self.active[lane] = False
            self._prefilling[lane] = False
            self._lane_rid[lane] = -1
            self._seq.pop(lane, None)
            self.alloc.free(rid)  # shared pins survive via their permanent ref
            self.scheduler.finish(rid, reason)
            self._dev_dirty = True
        return rid

    def _release_rid(self, rid: int) -> None:
        """Free the lane and pages of a cancelled *running* request
        (scheduler accounting already done by ``Scheduler.cancel``)."""
        for lane in np.flatnonzero(self._lane_rid == rid):
            self.active[lane] = False
            self._prefilling[lane] = False
            self._lane_rid[lane] = -1
            self._seq.pop(int(lane), None)
            self._dev_dirty = True
        if rid == self._shared_pending_rid:
            self._shared_pending_rid = -1  # its prefix pages never landed
        self.alloc.free(rid)

    def cancel(self, rid: int, reason: str = "cancelled") -> None:
        """Abort an unfinished request immediately: a queued rid is
        dequeued without admission, a running rid's lane and pages free
        for the next admission.  Emitted tokens stay in ``outputs``."""
        if not self.scheduler.cancel(rid, reason):
            self._release_rid(rid)

    def _shed_expired(self) -> list[int]:
        """Cancel every deadline-expired request (finish_reason
        "deadline") at this step boundary — see ``Engine._shed_expired``."""
        shed = []
        for rid in self.scheduler.expired():
            if not self.scheduler.cancel(rid, "deadline"):
                self._release_rid(rid)
            shed.append(rid)
        return shed

    def set_tier(self, tier: int) -> None:
        """Swap the active precision tier at a step boundary (same
        contract as ``Engine.set_tier``: weights and plan table repoint,
        KV pages and lane state untouched)."""
        if self.tiers is None:
            raise RuntimeError(
                "engine was built without a governor (ServeConfig.governor)"
            )
        if not 0 <= tier < len(self.tiers):
            raise ValueError(
                f"tier {tier} out of range [0, {len(self.tiers)})"
            )
        if tier == self.active_tier:
            return
        t = self.tiers[tier]
        self.params = t.params
        self.plan_table = t.plan_table
        self.active_tier = tier

    def _govern(self) -> None:
        if self.governor is None:
            return
        target = self.governor.observe(
            self.scheduler.n_queued,
            slow_step_ms=1e3 * self.straggler.rolling_median(),
        )
        if target != self.active_tier:
            self.set_tier(target)

    def step(self) -> list[int]:
        """Shed expired requests, let the governor re-tier, admit what
        fits, prefill one chunk per prefilling lane, advance the decode
        batch one token.  A lane that completed its prefill this step
        decodes from the NEXT step (the decode batch is snapshotted
        before the prefill phase).  Returns finished rids.

        The step is timed from its entry to the return of its last wait on
        the device (the decode ids, else the first-token ids); a step that
        dispatched work and waited on none is timed with the next one that
        waits.  That time feeds the straggler detector and the time of
        each phase the step carried (``stats``).  The step's layer
        boundaries are profiler spans (``serve.admit``, ``serve.prefill``,
        ``serve.decode`` and their ``serve.*`` children), each a few
        microseconds when no profile is being taken."""
        t0 = time.monotonic()
        sched = self.scheduler
        rows = (sched.prefill_rows, sched.decode_rows)
        self._t_synced = None
        self._shed_expired()
        self._govern()
        self._admit_new()
        decode_mask = (self.active & ~self._prefilling).copy()
        finished = self._prefill_step()
        finished += self._decode_once(decode_mask)
        start, prefill, decode = self._unsynced or (t0, False, False)
        prefill |= sched.prefill_rows > rows[0]
        decode |= sched.decode_rows > rows[1]
        self._unsynced = None
        if self._t_synced is not None:
            dt = self._t_synced - start
            sched.note_step(dt, prefill, decode)
            self.straggler.record(0, dt)
        elif prefill or decode:
            self._unsynced = (start, prefill, decode)
        return finished

    def generate(self, prompts: list[list[int]], max_new: int | None = None,
                 sampling: SamplingParams | None = None) -> dict[int, list[int]]:
        """Drive a batch of prompts to completion (reference loop)."""
        rids = [self.submit(p, max_new=max_new, sampling=sampling, admit=False)
                for p in prompts]
        per_req = max_new if max_new is not None else self.scfg.max_new
        # prefill costs ceil(L/chunk) steps per request; double for
        # preemption re-prefills under page pressure
        budget = 2 * (
            per_req * len(prompts)
            + sum(-(-len(p) // self._chunk) for p in prompts)
            + len(prompts)
        ) + 8
        for _ in range(budget):
            if not (self.active.any() or self.scheduler.n_queued):
                break
            self.step()
        assert not (self.active.any() or self.scheduler.n_queued), \
            "generate() exceeded its step budget"
        return {r: list(self.scheduler.requests[r].tokens) for r in rids}

    # ---- introspection --------------------------------------------------
    def drain_stream(self) -> list[tuple[int, int]]:
        """Pop every ``(rid, token)`` emitted since the last drain, in
        emission order — the streaming-output hook."""
        out = list(self._stream)
        self._stream.clear()
        return out

    @property
    def outputs(self) -> dict[int, list[int]]:
        """rid -> tokens emitted so far (see ``Engine.outputs``);
        preempted requests keep their pre-preemption tokens — resume
        appends to the same list."""
        return {r.rid: r.tokens for r in self.scheduler.requests.values()
                if r.tokens}

    def stats(self) -> dict:
        """``Engine.stats`` plus the page-pool gauges (total/free pages,
        page size, admission watermark) and the straggler detector's
        rolling-median step time (``median_step_s``).

        A step runs both phases, so ``prefill_tok_s`` and ``decode_tok_s``
        are each phase's tokens over the summed time of the steps that
        carried that phase (``step``): a step carrying both counts in
        both, so neither is the rate of its phase alone."""
        s = self.scheduler.stats()
        s.update(
            n_pages=self.alloc.n_pages,
            free_pages=self.alloc.n_free,
            page_size=self.alloc.page_size,
            watermark_pages=self.alloc.watermark,
            median_step_s=self.straggler.rolling_median(),
        )
        if self.governor is not None:
            s["governor"] = dict(
                self.governor.stats(),
                tier_name=self.tiers[self.active_tier].name,
            )
        if self.plan_db_stats is not None:
            s["plan_db"] = dict(self.plan_db_stats)
        return s
