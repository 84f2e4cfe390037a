"""Tensor-parallel packed serving: ``shard_map`` over prepacked weights.

This module makes the serving engines' quantized weight trees
mesh-parallel while keeping every emitted token bit-identical to the
single-device engine.  The partitioning follows the Megatron conventions
shared with the training policy (``runtime.sharding.linear_partition``,
DESIGN.md §4): column-parallel linears shard their output channels over
the mesh "model" axis and need no reduction; row-parallel linears shard
the contraction axis and reduce once per call.

The load-bearing invariant is WHERE the row-parallel reduction happens
(DESIGN.md §4, "the packed-word reduction invariant"): for packed plans
it runs in **int32 packed-word space** — each shard accumulates its own
pair products into packed partial words, a ``psum`` adds the words
across devices (int32 wrapping addition is associative and commutative,
so the sum is order-independent bit-for-bit), and field extraction +
correction run ONCE on the reduced word.  That is exactly the arithmetic
of a single device running the *widened* plan
(``kernels.ref.widen_for_shards``: the plan with ``n_shards * n_pairs``
products per extraction group), so the sharding is legal if and only if
the widened spec is constructible — the ``PackedDotSpec`` constructor's
int32-accumulator / middle-field / aliasing clauses
(``analysis.clauses``) reject an overflowing sharding at build with the
violated clause named, the same way they reject an illegal ``n_pairs``.
``shard_params_tp`` additionally re-proves the widened spec through
``analysis.verify.certify_spec`` so every row-sharded leaf carries a
machine-checked certificate of the cross-device accumulation budget.

Bit-identity per path:

* **row, int4 nibbles**: the activation row is quantized outside
  ``shard_map``; each shard runs the exact int8 × int4 integer matmul on
  its own packed rows and an int32 ``psum`` reduces — order-independent.
* **row, proven-exact plans** (the CPU serving default): the activation
  row is quantized OUTSIDE ``shard_map`` (the per-row scale must see
  every channel), the f32 GEMM runs per K-shard and a f32 ``psum``
  reduces.  Every partial sum is an exact small integer below the f32
  mantissa bound (guarded at prepack), so the reduction is exact in any
  order — bit-identical to the unsharded GEMM.
* **row, word path** (mr/overpacked plans, no f32 shortcut): the psum
  runs on int32 words pre-extraction as above; mr contamination terms
  psum the same way (residues mod ``2**mr_bits`` compose:
  ``(a mod r + b mod r) mod r == (a+b) mod r``).  The result is
  bit-identical to a single device running the widened spec — the
  shard-aware planner (``tuning.rank_plans(shard_groups=...)``) scores
  plans on exactly that widened arithmetic.
* **col**: each shard runs the full single-device arithmetic on its own
  output channels (integer work is channel-independent; the activation
  quantize is a replicated computation of replicated inputs) and an
  ``all_gather(tiled=True)`` reassembles channels in device order.

Outputs leave every ``shard_map`` fully replicated — downstream norms
and residuals see the same f32 values the single-device engine sees, so
XLA cannot reassociate a reduction differently per mesh shape.

Float ("native") weight trees pass through unwrapped: f32 matmul
reductions are not associative, so float leaves replicate — packed
integer representations are precisely what makes tensor-parallel decode
bit-exact (the thesis of DESIGN.md §4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels import ref
from .sharding import linear_partition

__all__ = ["TpLinear", "shard_params_tp", "apply_tp_linear"]


@jax.tree_util.register_pytree_node_class
class TpLinear:
    """A mesh-partitioned serving linear.

    Wraps one quantized weight leaf (a ``DspTunedLeaf`` or an int4
    ``{"packed","scale"}`` dict) whose arrays were ``device_put``
    onto the mesh by :func:`shard_params_tp`.  The wrapper is a pytree
    node — the inner leaf's arrays are children (so ``lax.scan`` over
    stacked scan groups slices through it and checkpoint/eval_shape
    walks see the real arrays) while the partition kind, shard count and
    mesh ride the treedef as static aux, making every jitted engine step
    specialize per sharding exactly like it specializes per plan.

    ``core.packed_linear.apply_linear`` dispatches wrapped leaves to
    :func:`apply_tp_linear` instead of the single-device arithmetic.
    """

    def __init__(self, inner, *, kind: str, mesh, n_shards: int,
                 axis: str = "model"):
        if kind not in ("col", "row"):
            raise ValueError(f"kind {kind!r} not in ('col', 'row')")
        self.inner = inner
        self.kind = kind
        self.mesh = mesh
        self.n_shards = n_shards
        self.axis = axis

    def tree_flatten(self):
        return (self.inner,), (self.kind, self.mesh, self.n_shards, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = cls.__new__(cls)
        (obj.inner,) = children
        obj.kind, obj.mesh, obj.n_shards, obj.axis = aux
        return obj


def _last_axis_pspec(arr, axis: str) -> P:
    return P(*([None] * (arr.ndim - 1) + [axis]))


def _put(mesh, arr, spec: P):
    return None if arr is None else jax.device_put(arr, NamedSharding(mesh, spec))


# ---- wrapping (engine build) ----------------------------------------------


def _widened_grouping(arr, S: int, chunk_axis: int, pairs_axis: int):
    """Regroup packed per-chunk operands onto the WIDENED chunk grid.

    ``pack_weight_words`` lays pair words out as (..., n_chunks, n_pairs,
    ...); the widened plan's extraction group is ``S`` consecutive local
    chunks, so the widened layout is a pure reshape — (…, C, S·n_pairs, …)
    with ``C = n_chunks / S`` — after zero-padding the chunk axis to a
    multiple of ``S`` (zero pairs are bit-transparent in every correction
    scheme, see ``ref._pad_k``).  Shard slice ``d`` of the merged pairs
    axis is then exactly local chunk ``c·S + d`` of every widened chunk
    ``c`` — each device owns whole local chunks.
    """
    n_chunks = arr.shape[chunk_axis]
    pad = (-n_chunks) % S
    if pad:
        widths = [(0, 0)] * arr.ndim
        widths[chunk_axis] = (0, pad)
        arr = jnp.pad(arr, widths)
    c = (n_chunks + pad) // S
    shape = list(arr.shape)
    shape[chunk_axis] = c
    shape[pairs_axis] = S * shape[pairs_axis]
    return arr.reshape(shape)


def _wrap_tuned(leaf, path: str, mesh, S: int, axis: str):
    from ..analysis.verify import certify_spec
    from ..core.packed_params import DspTunedLeaf

    kind = linear_partition(path)
    if kind is None or leaf.words is None:
        # unnamed role, or a storage-only (prepack=False) leaf whose
        # apply path repacks per step: replicate
        return leaf

    if kind == "col":
        n = leaf.scale.shape[-1]
        if n % S:
            return leaf  # replicate fallback, mirroring param_pspec
        last = lambda a: _last_axis_pspec(a, axis)  # noqa: E731
        new = DspTunedLeaf(
            payload=_put(mesh, leaf.payload, last(leaf.payload)),
            scale=_put(mesh, leaf.scale, last(leaf.scale)),
            spec=leaf.spec, block=leaf.block,
            decode_block=leaf.decode_block, exact=leaf.exact,
            words=_put(mesh, leaf.words, last(leaf.words)),
            wsc=(None if leaf.wsc is None
                 else _put(mesh, leaf.wsc, last(leaf.wsc))),
            zp_row=_put(mesh, leaf.zp_row, last(leaf.zp_row)),
            w_f32=(None if leaf.w_f32 is None
                   else _put(mesh, leaf.w_f32, last(leaf.w_f32))),
            prepack=False,
        )
        return TpLinear(new, kind="col", mesh=mesh, n_shards=S, axis=axis)

    # row: the contraction axis is sharded, so the cross-device reduction
    # accumulates S shards' worth of pair products in one packed word
    # BEFORE extraction — legal iff the widened spec is constructible.
    # widen_for_shards raises the constructor's clause-citing ValueError
    # for an overflowing sharding; certify_spec re-proves the legal case.
    try:
        wide = ref.widen_for_shards(leaf.spec, S)
    except ValueError as e:
        raise ValueError(
            f"illegal row sharding for {path!r}: {e}"
        ) from e
    certify_spec(wide)

    words = _widened_grouping(
        leaf.words, S, leaf.words.ndim - 3, leaf.words.ndim - 2
    )
    wsc = None
    if leaf.wsc is not None:
        wsc = _widened_grouping(leaf.wsc, S, leaf.wsc.ndim - 4,
                                leaf.wsc.ndim - 3)
    # shard the merged pairs axis: P(..., "model", None) for words
    w_spec = P(*([None] * (words.ndim - 2) + [axis, None]))
    wsc_spec = None if wsc is None else P(
        *([None] * (wsc.ndim - 3) + [axis, None, None])
    )
    w_f32 = leaf.w_f32
    f32_spec = None
    if w_f32 is not None:
        if w_f32.shape[-2] % S:
            w_f32 = None  # ragged K: serve the word path instead
        else:
            f32_spec = P(*([None] * (w_f32.ndim - 2) + [axis, None]))
    new = DspTunedLeaf(
        payload=leaf.payload, scale=leaf.scale, spec=leaf.spec,
        block=leaf.block, decode_block=leaf.decode_block, exact=leaf.exact,
        words=_put(mesh, words, w_spec),
        wsc=None if wsc is None else _put(mesh, wsc, wsc_spec),
        zp_row=leaf.zp_row,
        w_f32=None if w_f32 is None else _put(mesh, w_f32, f32_spec),
        prepack=False,
    )
    return TpLinear(new, kind="row", mesh=mesh, n_shards=S, axis=axis)


def _wrap_int4(leaf: dict, path: str, mesh, S: int, axis: str):
    """Partition an int4 leaf's nibbles: by output column (col) or by
    packed row pairs of the contraction axis (row).  Shards compute exact
    int32 partial sums off their own nibbles (no f32 grid is sharded)."""
    kind = linear_partition(path)
    packed, scale = leaf["packed"], leaf["scale"]
    if kind is None:
        return leaf
    if kind == "col":
        if scale.shape[-1] % S:
            return leaf  # replicate fallback, mirroring param_pspec
        new = {
            "packed": _put(mesh, packed, _last_axis_pspec(packed, axis)),
            "scale": _put(mesh, scale, _last_axis_pspec(scale, axis)),
        }
        return TpLinear(new, kind="col", mesh=mesh, n_shards=S, axis=axis)
    if packed.shape[-2] % S:
        return leaf
    new = {
        "packed": _put(
            mesh, packed, P(*([None] * (packed.ndim - 2) + [axis, None]))
        ),
        "scale": scale,
    }
    return TpLinear(new, kind="row", mesh=mesh, n_shards=S, axis=axis)


def shard_params_tp(params, mesh, *, axis: str = "model",
                    use_kernel: bool = False):
    """Partition a quantized serving tree over ``mesh``'s ``axis``.

    Walks the post-quantization tree, classifies each packed linear by
    ``linear_partition`` of its tree path, ``device_put``s its operands
    onto the mesh and wraps it in :class:`TpLinear`.  Leaves the policy
    does not name — and float leaves, whose f32 reductions are not
    order-independent — stay replicated.  Raises the certificate-clause-
    citing ``ValueError`` for a row sharding whose widened accumulation
    would overflow (see module docstring).

    ``use_kernel=True`` is rejected: tensor-parallel serving runs the jnp
    reference / f32-shortcut paths (the Pallas kernels have no
    cross-device reduction stage).
    """
    from ..core.packed_params import is_dsp_tuned_leaf, is_packed_leaf

    S = int(mesh.shape[axis])
    if S > 1 and use_kernel:
        raise ValueError(
            "tensor-parallel packed serving (tp > 1) runs the jnp "
            "reference paths; use_kernel=True is not supported"
        )
    if S == 1:
        return params

    def walk(tree, path=""):
        if is_dsp_tuned_leaf(tree):
            return _wrap_tuned(tree, path, mesh, S, axis)
        if is_packed_leaf(tree):
            return _wrap_int4(tree, path, mesh, S, axis)
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        return tree

    return walk(params)


# ---- apply (decode / prefill) ---------------------------------------------


def _tuned_col(w: TpLinear, x):
    from ..kernels import ops

    leaf = w.inner
    m = x.shape[0]
    specs = jax.tree.map(lambda a: _last_axis_pspec(a, w.axis), leaf)

    def body(xl, lf):
        local = ops.dsp_tuned_matmul_prepacked_f32(
            xl, lf.words, lf.wsc, lf.zp_row, lf.scale, lf.w_f32,
            spec=lf.spec, block=lf.block_for(m), use_kernel=False,
            exact_f32=lf.w_f32 is not None,
        )
        return jax.lax.all_gather(local, w.axis, axis=1, tiled=True)

    return shard_map(
        body, mesh=w.mesh, in_specs=(P(None, None), specs),
        out_specs=P(None, None), check_vma=False,
    )(x, leaf)


def _tuned_row(w: TpLinear, x):
    from ..core.quantize import quantize_unsigned

    leaf = w.inner
    spec = leaf.spec
    S = w.n_shards
    m = x.shape[0]

    if leaf.w_f32 is not None:
        # exact-f32 shard path: quantize the FULL activation row outside
        # the shard_map (the per-row scale sees every channel, exactly as
        # on one device), contract per K-shard, reduce in f32 — exact,
        # because every partial sum is an exact integer (mantissa bound
        # guarded at prepack) and exact sums are order-independent
        zp = 1 << (spec.bits_a - 1)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        x_scale = jnp.maximum(amax, 1e-8) / (zp - 1)
        q = jnp.round(x / x_scale) + zp

        def gemm(ql, wl):
            return jax.lax.psum(ql @ wl, w.axis)

        acc = shard_map(
            gemm, mesh=w.mesh,
            in_specs=(P(None, w.axis), P(w.axis, None)),
            out_specs=P(None, None), check_vma=False,
        )(q, leaf.w_f32)
        acc = acc - leaf.zp_row.astype(jnp.float32)[None, :]
        return acc * x_scale * leaf.scale

    # packed-word path (mr / overpacked plans): the reduction runs on
    # int32 words BEFORE extraction — the widened-spec arithmetic the
    # build certified (module docstring).
    xq = quantize_unsigned(x, bits=spec.bits_a, axis=-1)
    x_u = xq.values.astype(jnp.int32)
    kw = leaf.words.shape[-3] * S * spec.chunk  # the widened chunk grid
    pad = kw - x_u.shape[1]
    if pad:
        x_u = jnp.pad(x_u, ((0, 0), (0, pad)))

    def body(xl, words, wsc):
        # words: (C, n_pairs, n) — this shard's slice of every widened
        # chunk's merged pairs axis (= local chunk c*S + shard_index)
        idx = jax.lax.axis_index(w.axis)
        npair = spec.n_pairs
        c, _, n = words.shape
        acc = jnp.zeros((xl.shape[0], n), jnp.int32)
        for j in range(spec.n_columns):
            xa = ref.slice_column(xl, spec, j).reshape(xl.shape[0], kw // 2, 2)
            a_words = (xa[:, :, 0] + (xa[:, :, 1] << spec.p)).reshape(
                xl.shape[0], c, S * npair
            )
            a_local = jax.lax.dynamic_slice_in_dim(
                a_words, idx * npair, npair, axis=2
            )
            partial = jax.lax.dot_general(
                a_local, words, (((2,), (1,)), ((1,), (0,))),
                preferred_element_type=jnp.int32,
            )
            # int32 wrapping addition is associative/commutative: the
            # psum'd word is bit-identical to one device accumulating
            # all S*n_pairs products (the widened spec's word)
            partial = jax.lax.psum(partial, w.axis)
            contam = None
            if spec.uses_mr:
                xa4 = xa.reshape(xl.shape[0], c, S * npair, 2)
                xa_l = jax.lax.dynamic_slice_in_dim(
                    xa4, idx * npair, npair, axis=2
                )
                # residues mod 2**mr_bits compose across shards:
                # psum the masked local terms, re-mask once
                contam = jax.lax.psum(
                    ref.contamination_terms(xa_l, wsc, spec), w.axis
                ) & jnp.int32(ref.contamination_mask(spec))
            # extraction parameters (p / extract width / correction) are
            # identical between the local and widened spec — n_pairs only
            # sizes the accumulation the psum just performed
            field = ref.extract_accumulated_field(partial, spec, contam)
            col = jnp.sum(field, axis=0)
            shift = spec.column_shift(j)
            acc = acc + (col << shift if shift else col)
        return acc

    if spec.uses_mr:
        acc = shard_map(
            body, mesh=w.mesh,
            in_specs=(P(None, None), P(None, w.axis, None),
                      P(None, w.axis, None, None)),
            out_specs=P(None, None), check_vma=False,
        )(x_u, leaf.words, leaf.wsc)
    else:
        acc = shard_map(
            lambda xl, ww: body(xl, ww, None), mesh=w.mesh,
            in_specs=(P(None, None), P(None, w.axis, None)),
            out_specs=P(None, None), check_vma=False,
        )(x_u, leaf.words)
    acc = acc - leaf.zp_row[None, :]
    return acc.astype(jnp.float32) * xq.scale * leaf.scale


def _int4_col(w: TpLinear, x):
    from ..kernels import ops

    d = w.inner

    def body(xl, packed, scale):
        local = ops.int4_matmul_f32(xl, packed, scale, use_kernel=False)
        return jax.lax.all_gather(local, w.axis, axis=1, tiled=True)

    return shard_map(
        body, mesh=w.mesh,
        in_specs=(P(None, None), P(None, w.axis), P(None, w.axis)),
        out_specs=P(None, None), check_vma=False,
    )(x, d["packed"], d["scale"])


def _int4_row(w: TpLinear, x):
    from ..core.quantize import quantize_signed

    d = w.inner
    # quantize the FULL activation row outside the shard_map (the per-row
    # scale sees every channel, exactly as on one device); each shard's
    # exact int32 partial sum is psummed — integer sums are
    # order-independent, so the result is the one-device integer matmul
    xq = quantize_signed(x, bits=8, axis=-1)

    def gemm(ql, wl):
        return jax.lax.psum(ref.ref_int4_matmul(ql, wl), w.axis)

    acc = shard_map(
        gemm, mesh=w.mesh,
        in_specs=(P(None, w.axis), P(w.axis, None)),
        out_specs=P(None, None), check_vma=False,
    )(xq.values, d["packed"])
    return acc.astype(jnp.float32) * xq.scale * d["scale"]


def apply_tp_linear(w: TpLinear, x, quant_spec):
    """Serve one wrapped linear: (m, d_in) float → (m, d_out) float.

    The tensor-parallel counterpart of the ``apply_linear`` packed
    branches — same quantize recipes, same scales, with the contraction
    reduced across the mesh per the module-docstring invariant.  Returns
    a fully replicated array (bit-identity contract).
    """
    from ..core.packed_params import is_dsp_tuned_leaf

    if getattr(quant_spec, "use_kernel", False):
        raise ValueError(
            "tensor-parallel serving runs the jnp reference paths; "
            "use_kernel=True is rejected at engine build"
        )
    # Pin the activation to fully-replicated BEFORE any TP arithmetic.
    # Without this anchor GSPMD back-propagates the shard_map's
    # P(None, "model") input spec through the quantize into the upstream
    # attention/MLP math, partitioning ops (rope, cache scatter) that
    # must stay replicated for bit-identity — observed as gross (O(1))
    # divergence on the 8-way host mesh, not mere reassociation noise.
    # One constraint at the boundary = one reshard, and everything
    # upstream compiles exactly as the single-device engine does.
    x = jax.lax.with_sharding_constraint(
        x, NamedSharding(w.mesh, P(None, None))
    )
    if is_dsp_tuned_leaf(w.inner):
        return _tuned_col(w, x) if w.kind == "col" else _tuned_row(w, x)
    return _int4_col(w, x) if w.kind == "col" else _int4_row(w, x)
