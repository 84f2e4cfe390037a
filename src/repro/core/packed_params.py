"""Packed-weight parameter trees for serving.

`quantize_params_for_serving` converts every matmul weight into the paper's
packed-density representation: int4 values, two per uint8 byte (+ per-output
-channel f32 scale).  This is the framework-level translation of DSP-packing
for TPU serving (DESIGN.md §2): weight HBM bytes drop 4× vs bf16, which both
(a) moves the decode roofline's memory term down and (b) lets models that
needed per-step FSDP gathers fit TP-only-replicated — removing the per-token
parameter all-gather entirely (EXPERIMENTS.md §Perf, cells A/C).

``dsp_tuned`` is the per-layer generalization: the ``repro.tuning`` planner
picks, per weight, the fastest pair-packed plan inside an error budget, and
the weight is quantized ONCE to the plan's signed integer grid and stored in
a :class:`DspTunedLeaf` — a registered pytree node that carries the plan
(spec + block) as static aux data, so jitted serving programs specialize on
the plan without retracing per call.

The leaf separates STORAGE from COMPUTE operands (the prepacked decode fast
path):

* storage — ``payload``: the signed plan grid nibble-packed two values per
  uint8 byte when ``bits_w <= 4`` (sub-byte storage, 2× denser than the old
  int8 store), plain int8 otherwise.  ``values`` decodes it on demand.
* compute — ``words``/``wsc``: the pair-packed int32 weight words (and, for
  mr plans only, the contamination operands) from
  ``kernels.ref.pack_weight_words``, built ONCE at engine build so no decode
  step ever repacks; ``zp_row``: the precomputed zero-point correction
  ``zp·Σ_k w``; ``w_f32``: the signed grid cast to f32, built only off-TPU
  — on backends whose integer dots lower to scalar loops (CPU),
  *provably exact* plans run the identical integer matmul through the
  fast f32 GEMM unit, bit-for-bit (``ref.exact_int_matmul_fits_f32``); on a
  TPU the Pallas kernel serves the words.

The storage-vs-HBM tradeoff is explicit: ``payload`` is what a checkpoint /
HBM-resident copy costs (0.5–1 byte per value), the prepacked operands are
a decode-speed cache costing extra device bytes (2 bytes per value for
``words``, +4 for mr ``wsc``, +4 for ``w_f32``).  ``prepack=False`` keeps
storage only.

Norms, biases, embeddings and 1-D leaves stay bf16 (gather tables and
vector ops gain nothing from nibble packing).
"""

from __future__ import annotations

import functools
from typing import Any, Iterator

import jax
import jax.numpy as jnp

from ..kernels import ref
from ..kernels.ops import on_tpu
from ..kernels.ref import INT4_EXACT, PackedDotSpec
from .quantize import quantize_signed, zero_point_correction

__all__ = [
    "quantize_params_for_serving",
    "quantize_for_serving",
    "fuse_projection_weights",
    "is_packed_leaf",
    "is_dsp_tuned_leaf",
    "iter_packable_weights",
    "split_expert_stacks",
    "pack_signed_nibbles",
    "unpack_signed_nibbles",
    "DspTunedLeaf",
    "SERVING_MODES",
]

MIN_DIM = 32  # don't pack tiny matrices (router tables etc. stay exact)

# Weight-conversion modes accepted by the serving engine.  Storage packing
# happens for ``int4_packed`` (nibbles) and ``dsp_tuned``/``dsp_mixed``
# (per-layer plan integers — ``dsp_mixed`` is ``dsp_tuned`` with a
# sensitivity-allocated per-layer width map, see ``tuning.mixed``);
# ``int8``/``dsp_packed`` keep float weights and quantize at the point of
# use (their arithmetic is selected via ``LinearSpec.mode``), and
# ``native``/``none`` serve the weights as-is.
SERVING_MODES = ("native", "none", "int8", "int4_packed", "dsp_packed",
                 "dsp_tuned", "dsp_mixed")


def is_packed_leaf(p) -> bool:
    return isinstance(p, dict) and "packed" in p and "scale" in p


def is_dsp_tuned_leaf(p) -> bool:
    return isinstance(p, DspTunedLeaf)


# ---- sub-byte storage -----------------------------------------------------


def pack_signed_nibbles(v: jax.Array) -> jax.Array:
    """(…, K, N) signed ints in [-8, 7] → (…, K//2, N) uint8 nibbles.

    The generalization of ``ref.pack_int4_weights`` to any leading batch
    shape — the storage layout of every ``bits_w <= 4`` plan grid."""
    v = jnp.asarray(v, jnp.int8)
    k = v.shape[-2]
    if k % 2:
        raise ValueError("K must be even to pack nibbles")
    lo = v[..., 0::2, :] & 0xF
    hi = v[..., 1::2, :] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_signed_nibbles(packed: jax.Array) -> jax.Array:
    """(…, K//2, N) uint8 → (…, K, N) int8, sign-extended — the exact
    inverse of :func:`pack_signed_nibbles` on the signed grid."""
    b = packed.astype(jnp.int8)
    lo = (b << 4) >> 4  # arithmetic shift sign-extends the low nibble
    hi = b >> 4
    k2, n = packed.shape[-2:]
    out = jnp.stack([lo, hi], axis=-2)  # (..., K/2, 2, N)
    return out.reshape(packed.shape[:-2] + (2 * k2, n))


# ---- the tuned-plan leaf --------------------------------------------------


@jax.tree_util.register_pytree_node_class
class DspTunedLeaf:
    """A matmul weight quantized once to a tuned packing plan.

    Constructed from ``values`` ((…, d_in, d_out) signed ints on the plan's
    ``bits_w`` grid) and ``scale`` ((…, 1, d_out) f32); stores the nibble/
    int8 ``payload`` plus, when ``prepack=True`` (the default), the
    device-resident prepacked compute operands described in the module
    docstring.  ``spec``/``block``/``decode_block`` are static aux data —
    part of the treedef, so jitted programs specialize per plan.
    ``exact`` marks plans PROVEN error-free (algebraically or by exhaustive
    enumeration), unlocking the f32-GEMM fast path where it is bit-safe.
    """

    def __init__(self, values=None, scale=None, spec: PackedDotSpec = None,
                 block=None, *, decode_block=None, exact: bool | None = None,
                 payload=None, words=None, wsc=None, zp_row=None, w_f32=None,
                 prepack: bool = True):
        if spec is None:
            raise ValueError("DspTunedLeaf needs its PackedDotSpec")
        self.scale = scale
        self.spec = spec
        self.block = tuple(block) if block is not None else None
        self.decode_block = (
            tuple(decode_block) if decode_block is not None else None
        )
        if exact is None:
            # the certificate is the authority (it proves exactness for a
            # superset of the constructor's provably_exact predicate)
            from ..analysis.verify import certify_spec

            exact = certify_spec(spec).exact
        self.exact = bool(exact)
        if payload is None:
            if values is None:
                raise ValueError("DspTunedLeaf needs values or payload")
            values = jnp.asarray(values)
            if spec.bits_w <= 4 and values.shape[-2] % 2 == 0:
                payload = pack_signed_nibbles(values)
            else:
                payload = values.astype(jnp.int8)
        self.payload = payload
        self.words = words
        self.wsc = wsc
        self.zp_row = zp_row
        self.w_f32 = w_f32
        if prepack and words is None and values is not None:
            self._prepack(values)

    @property
    def nibble_packed(self) -> bool:
        return self.payload.dtype == jnp.uint8

    @property
    def values(self) -> jax.Array:
        """The signed plan-grid integers, decoded from storage (int8)."""
        if self.nibble_packed:
            return unpack_signed_nibbles(self.payload)
        return self.payload

    def _prepack(self, values) -> None:
        """Build the compute operands once (engine build time), one layer
        of a stacked weight at a time."""
        spec = self.spec
        self.words, self.wsc, self.zp_row = _prepack_layers(values, spec)
        # the f32 shortcut is only bit-safe when the plan is proven exact
        # AND every partial sum fits the f32 mantissa, and only pays off
        # where integer dots lower to scalar loops: never on a TPU, where
        # the Pallas kernel serves the words
        k = values.shape[-2]
        max_a = (1 << spec.bits_a) - 1
        max_w = 1 << (spec.bits_w - 1)
        if (self.exact and not on_tpu()
                and ref.exact_int_matmul_fits_f32(k, max_a, max_w)):
            self.w_f32 = values.astype(jnp.float32)

    @property
    def prepacked(self) -> bool:
        return self.words is not None

    def block_for(self, m: int):
        """Phase-appropriate tuned block: decode GEMVs (small m) get the
        decode-tuned block, prefill the general one."""
        from ..kernels.packed_matmul import DECODE_BLOCK

        if m <= DECODE_BLOCK[0] and self.decode_block is not None:
            return self.decode_block
        return self.block

    def tree_flatten(self):
        children = (self.payload, self.scale, self.words, self.wsc,
                    self.zp_row, self.w_f32)
        aux = (self.spec, self.block, self.decode_block, self.exact)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        leaf = cls.__new__(cls)
        (leaf.payload, leaf.scale, leaf.words, leaf.wsc, leaf.zp_row,
         leaf.w_f32) = children
        leaf.spec, leaf.block, leaf.decode_block, leaf.exact = aux
        return leaf


def iter_packable_weights(
    params, min_dim: int = MIN_DIM, path: str = ""
) -> Iterator[tuple[str, Any]]:
    """Yield ``(tree_path, leaf)`` for every matmul weight eligible for
    packed serving — the single eligibility predicate shared by the weight
    converters here and the per-layer planner (``tuning.plan_linear_layers``),
    so plan tables and converted trees always agree on coverage."""
    if not isinstance(params, dict):
        return
    parent = path.rsplit("/", 1)[-1]
    for k, v in params.items():
        p = f"{path}/{k}"
        # per-expert leaves from ``split_expert_stacks`` ("up/e0", "down/e3"…)
        expert_leaf = (
            k.startswith("e") and k[1:].isdigit()
            and parent in ("up", "gate", "down")
        )
        if (
            (k in ("w", "up", "gate", "down") or expert_leaf)
            and hasattr(v, "ndim")
            and v.ndim >= 2
            and "embed" not in path
            and "patch_proj" not in path
            and "router" not in p  # keep routing exact (tiny)
            and v.shape[-2] >= min_dim
            and v.shape[-1] >= min_dim
            and v.shape[-2] % 2 == 0
        ):
            yield p, v
        else:
            yield from iter_packable_weights(v, min_dim, p)


def split_expert_stacks(params):
    """Split stacked MoE expert weights into per-expert leaves.

    ``init_moe`` stores each projection as one ``(…, E, d_in, d_out)``
    stack.  A single stack can only carry a single quantization plan, and a
    stacked packed leaf dequantizes at use (``apply_linear``'s prepacked
    fast path needs a 2-D payload).  Splitting the stack into
    ``{"e0": (…, d_in, d_out), "e1": …}`` children gives every expert its
    own tree path — its own plan, its own sensitivity row, its own
    prepacked leaf — and ``moe_ffn`` then routes each expert's capacity
    buffer through ``apply_linear``.  Expert stacks are recognized
    structurally: an ``up``/``gate``/``down`` array of ``ndim >= 3`` whose
    parent dict also holds a ``router`` (the expert axis is always
    third-from-last, under any outer layer stacking).  Idempotent — an
    already-split tree passes through unchanged.
    """
    if not isinstance(params, dict):
        return params
    out = {}
    is_moe = "router" in params
    for k, v in params.items():
        if (
            is_moe
            and k in ("up", "gate", "down")
            and hasattr(v, "ndim")
            and v.ndim >= 3
        ):
            out[k] = {f"e{i}": v[..., i, :, :] for i in range(v.shape[-3])}
        else:
            out[k] = split_expert_stacks(v)
    return out


# ---- projection fusion ----------------------------------------------------


def fuse_projection_weights(params, fuse_attn: bool = True,
                            fuse_mlp: bool = True):
    """Engine-build fusion of same-input projections (packed modes only).

    Attention's q/k/v and SwiGLU's up/gate each consume the same activation;
    concatenating their weights along the output axis at build time turns
    three (two) GEMVs per decode step into one, and — because both weight
    and activation quantization are per-output-channel / per-row — the fused
    quantized matmul is BIT-IDENTICAL per column to the unfused one.  Only
    self-attention blocks are fused (cross-attention's q and k/v read
    different inputs), recognized structurally: a dict holding wq/wk/wv
    sub-dicts under any key except ``xattn``.  Biases concatenate alongside.

    ``fuse_attn``/``fuse_mlp`` gate the two fusion sites independently: on
    backends where the fused qkv output must be re-sliced through the head
    reshape (CPU XLA), attention fusion can cost more than the saved GEMV
    dispatches, while up|gate fusion is a pure win — the serving engine maps
    its ``fuse_projections`` config onto these switches.
    """

    def is_linear(d):
        return isinstance(d, dict) and "w" in d and hasattr(d["w"], "ndim")

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            if (
                fuse_attn
                and k != "xattn"
                and isinstance(v, dict)
                and all(is_linear(v.get(n)) for n in ("wq", "wk", "wv"))
            ):
                v = dict(v)
                parts = [v.pop("wq"), v.pop("wk"), v.pop("wv")]
                fused = {"w": jnp.concatenate([p["w"] for p in parts], axis=-1)}
                if all("b" in p for p in parts):
                    fused["b"] = jnp.concatenate(
                        [p["b"] for p in parts], axis=-1
                    )
                out[k] = {"wqkv": fused, **{n: walk(s) for n, s in v.items()}}
            elif (
                fuse_mlp
                and isinstance(v, dict)
                and all(is_linear(v.get(n)) for n in ("up", "gate", "down"))
                and v["up"]["w"].shape == v["gate"]["w"].shape
            ):
                v = dict(v)
                up, gate = v.pop("up"), v.pop("gate")
                fused = {"w": jnp.concatenate([up["w"], gate["w"]], axis=-1)}
                if "b" in up and "b" in gate:
                    fused["b"] = jnp.concatenate([up["b"], gate["b"]], axis=-1)
                out[k] = {"upgate": fused, **{n: walk(s) for n, s in v.items()}}
            else:
                out[k] = walk(v)
        return out

    return walk(params)


def map_layers(fn, w: jax.Array):
    """Apply ``fn`` to each (d_in, d_out) matrix of a (…, d_in, d_out)
    stack, one matrix at a time (``lax.map``), so a conversion's
    temporaries — an f32 copy, int32 words — exist for one layer, never
    for the whole stack.  Outputs keep the stack's leading axes; ``None``
    outputs pass through."""
    lead = w.shape[:-2]
    if not lead:
        return fn(w)
    out = jax.lax.map(fn, w.reshape((-1,) + w.shape[-2:]))
    return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), out)


# The engine-build conversions, jitted per (shape, plan) so repeated builds
# reuse one compiled program per weight shape.


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_layers(w: jax.Array, bits: int):
    """Per-layer signed quantization → (int8 values, f32 scale)."""

    def one(m):
        q = quantize_signed(m.astype(jnp.float32), bits=bits, axis=0)
        return q.values, q.scale

    return map_layers(one, w)


@jax.jit
def _pack_int4_layers(w: jax.Array):
    """Per-layer int4 quantization → (uint8 nibbles, f32 scale)."""

    def one(m):
        q = quantize_signed(m.astype(jnp.float32), bits=4, axis=0)
        return ref.pack_int4_weights(q.values), q.scale

    return map_layers(one, w)


@functools.partial(jax.jit, static_argnames=("spec",))
def _prepack_layers(values: jax.Array, spec: PackedDotSpec):
    """Per-layer compute operands of a tuned plan → (words, wsc, zp_row)."""
    zp = 1 << (spec.bits_a - 1)

    def one(m):
        m = m.astype(jnp.int32)
        packed = ref.pack_weight_words(m, spec)
        return packed.words, packed.wsc, zero_point_correction(m, zp)

    return map_layers(one, values)


def _pack_matrix(w: jax.Array, prepack: bool = False) -> dict:
    """(…, d_in, d_out) float -> packed int4 nibbles + per-channel scale.

    ``prepack=True`` (engine build) additionally stores ``w_f32`` — the
    int4 grid decoded once and cast to f32 — so the decode fast path runs
    the exact int8×int4 matmul through the f32 GEMM unit instead of
    unpacking nibbles and looping an integer dot every step.  Not on a
    TPU: there the Pallas kernel serves the nibbles directly."""
    d_in = w.shape[-2]
    packed, scale = _pack_int4_layers(w)
    leaf = {"packed": packed, "scale": scale}
    if (prepack and not on_tpu()
            and ref.exact_int_matmul_fits_f32(d_in, 128, 8)):
        leaf["w_f32"] = unpack_signed_nibbles(packed).astype(jnp.float32)
    return leaf


def _tune_matrix(w: jax.Array, spec: PackedDotSpec,
                 block: tuple[int, int, int] | None,
                 decode_block: tuple[int, int, int] | None = None,
                 exact: bool | None = None,
                 prepack: bool = True) -> DspTunedLeaf:
    """(…, d_in, d_out) float -> plan-grid signed ints + per-channel scale
    (+ the prepacked compute operands when ``prepack``)."""
    values, scale = _quantize_layers(w, spec.bits_w)
    return DspTunedLeaf(
        values=values,
        scale=scale,
        spec=spec,
        block=block,
        decode_block=decode_block,
        exact=exact,
        prepack=prepack,
    )


def dequantize_packed(p: dict, dtype=jnp.bfloat16) -> jax.Array:
    """Graph-level unpack: two arithmetic shifts + scale.  On real TPU the
    Pallas kernel (`kernels/int4_matmul.py`) does this inside VMEM; the
    jnp path is the portable equivalent with the same HBM byte profile."""
    w = unpack_signed_nibbles(p["packed"])
    shape = p["packed"].shape[:-2] + (2 * p["packed"].shape[-2], p["packed"].shape[-1])
    return (w.reshape(shape).astype(jnp.float32) * p["scale"]).astype(dtype)


def materialize_weight(p, dtype):
    if is_packed_leaf(p):
        return dequantize_packed(p, dtype)
    if is_dsp_tuned_leaf(p):
        return (p.values.astype(jnp.float32) * p.scale).astype(dtype)
    return p


def _convert_tree(params, paths_to_convert: dict, convert):
    """Replace the leaves named in ``paths_to_convert`` (path -> per-leaf
    conversion argument); everything else passes through untouched."""

    def walk(tree, path=""):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            p = f"{path}/{k}"
            if p in paths_to_convert:
                out[k] = convert(v, paths_to_convert[p])
            else:
                out[k] = walk(v, p)
        return out

    return walk(params)


def quantize_params_for_serving(params, min_dim: int = MIN_DIM,
                                prepack: bool = False):
    """Replace every large matmul weight leaf 'w' (and MoE expert stacks)
    with its packed representation.  Tree structure changes: callers use
    the transformed tree for sharding/eval_shape as well.

    ``prepack=False`` (default) stores nibbles only — the checkpoint/HBM
    density representation; the engine passes ``prepack=True`` to also
    build the decode-speed operands."""
    params = split_expert_stacks(params)
    targets = {p: None for p, _ in iter_packable_weights(params, min_dim)}
    return _convert_tree(
        params, targets, lambda w, _: _pack_matrix(w, prepack=prepack)
    )


def quantize_for_serving(params, mode: str = "int4_packed",
                         min_dim: int = MIN_DIM, plans=None,
                         prepack: bool = True, only_planned: bool = False):
    """Engine-build-time weight conversion step.

    ``int4_packed`` packs every large matmul weight to nibbles *once*; the
    decode path (`packed_linear.apply_linear`) then runs the packed matmul
    straight off the stored representation every step — no per-call
    re-quantization, and (with ``prepack``, the engine default) no per-step
    unpacking either.

    ``dsp_tuned`` quantizes each weight to its tuned plan (``plans``: a
    ``{tree_path: PlanReport-or-spec}`` table from
    ``tuning.plan_linear_layers``; paths missing from the table fall back
    to the exact int4 preset) and stores :class:`DspTunedLeaf` leaves —
    nibble/int8 payload plus prepacked pair words — so decode runs
    per-layer pair-packed arithmetic off operands packed once.  The plan
    map is genuinely per layer: entries may carry different ``(a_bits,
    w_bits)`` — each leaf quantizes onto ITS spec's grid and serves its
    own arithmetic (the ``dsp_mixed`` mode is exactly this with a
    sensitivity-allocated width map from ``tuning.mixed``).
    ``only_planned=True`` converts ONLY the paths named in ``plans`` and
    leaves every other weight float — the single-layer probe the
    sensitivity pass runs.

    The other modes keep float weights (``int8`` and ``dsp_packed``
    quantize at the point of use through their ``LinearSpec.mode``
    arithmetic; ``native``/``none`` are pass-through).
    """
    if mode not in SERVING_MODES:
        raise ValueError(f"serving mode {mode!r} not in {SERVING_MODES}")
    if mode not in ("native", "none"):
        # MoE expert stacks get per-expert leaves under every quantizing
        # mode (int8/dsp_packed quantize per expert at the point of use)
        params = split_expert_stacks(params)
    if mode == "int4_packed":
        return quantize_params_for_serving(
            params, min_dim=min_dim, prepack=prepack
        )
    if mode in ("dsp_tuned", "dsp_mixed"):
        plans = plans or {}
        targets = {}
        for p, _ in iter_packable_weights(params, min_dim):
            plan = plans.get(p)
            if plan is None:
                if only_planned:
                    continue
                spec, block, dblock, exact = INT4_EXACT, None, None, None
            elif isinstance(plan, PackedDotSpec):
                spec, block, dblock, exact = plan, None, None, None
            else:  # tuning.PlanReport
                spec, block = plan.spec, plan.block
                dblock = getattr(plan, "decode_block", None)
                cert = getattr(plan, "certificate", None)
                exact = (cert.exact if cert is not None
                         else plan.spec.provably_exact) or (
                    plan.mae == 0 and plan.exhaustive
                )
            targets[p] = (spec, block, dblock, exact)
        return _convert_tree(
            params, targets,
            lambda w, t: _tune_matrix(w, t[0], t[1], t[2], t[3],
                                      prepack=prepack),
        )
    return params
