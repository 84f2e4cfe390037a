"""Pure-jnp oracles for the Pallas kernels.

Two compute paths (see DESIGN.md §2):

* pair-packed "DSP-sim" matmul — the paper-faithful adaptation.  Activations
  (unsigned, offset-binary) and weights (signed) are packed in pairs along K
  into int32 words; ONE int32 multiply per pair produces the pair's
  dot-product contribution in the middle bit field (the dot-product variant
  of the paper's Eqn. 4: the outer-product cross terms land in the low/high
  fields).  ``n_pairs`` words are accumulated before the field is extracted,
  mirroring the paper's ``2**delta`` accumulation budget.  Multi-DSP
  *column* packing (``PackedDotSpec.n_columns``, the wide-datapath related
  work's decomposition) splits the activation into unsigned bit-slices, one
  packed-word stream per slice, and recombines the extracted dot fields by
  shifted summation — lifting the int32 ceiling to 8-bit operands.

* packed-storage int4 matmul — the production path: weights live in HBM as
  two nibbles per byte (the *memory* translation of packing density), are
  unpacked in VMEM and fed to the int8 MXU path.

``ref_packed_matmul`` is bit-accurate to the kernel (same chunking,
extraction and correction arithmetic) so kernels are tested for *bit
equality*, errors included.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import clauses

__all__ = [
    "PackedDotSpec",
    "PackedWeightWords",
    "CORRECTIONS",
    "INT4_EXACT",
    "INT4_NAIVE",
    "INT4_MR_OVERPACKED",
    "INT2_EXACT",
    "widen_for_shards",
    "extract_accumulated_field",
    "contamination_mask",
    "contamination_term",
    "contamination_terms",
    "slice_column",
    "pack_weight_words",
    "packed_tile_matmul",
    "packed_tile_matmul_prepacked",
    "ref_packed_matmul",
    "ref_packed_matmul_prepacked",
    "ref_quantized_matmul",
    "exact_int_matmul_fits_f32",
    "pack_int4_weights",
    "unpack_int4_weights",
    "ref_int4_matmul",
]

# Correction schemes of the pair-packed dot path, mirroring
# ``core.correction.SCHEMES`` (the ``approx`` C-port scheme has no dot-product
# analogue — the accumulated middle field carries its own sign):
#   * ``naive``   — floor extraction (biased, Xilinx white-paper semantics)
#   * ``full``    — round-half-up extraction, bit-exact for legal specs (§V-A)
#   * ``mr``      — overpacked spacing, naive extraction + MSB restore (§VI-B)
#   * ``mr+full`` — MSB restore *and* round-half-up (beyond-paper combination)
CORRECTIONS = ("naive", "full", "mr", "mr+full")


@dataclasses.dataclass(frozen=True)
class PackedDotSpec:
    """Parameters of the pair-packed int32 dot path.

    ``p``        — field spacing in bits (the paper's result width + δ).
    ``n_pairs``  — packed products accumulated per extraction
                   (the paper's ``2**delta`` accumulation budget).
    ``correction`` — one of :data:`CORRECTIONS`.
    ``mr_bits``  — overlap bits restored in the ``mr``/``mr+full`` modes
                   (how far below the exact spacing ``p`` was squeezed).
    ``n_columns`` — multi-DSP column packing (the wide-datapath related
                   work's column decomposition): the activation's ``bits_a``
                   bits are split into ``n_columns`` unsigned bit-slices of
                   :attr:`col_bits_a` bits each, every slice runs its own
                   packed-word stream ("column") against the SHARED packed
                   weights, and the per-column extracted dot fields are
                   recombined as ``Σ_j field_j << (j·col_bits_a)``.  All
                   legality budgets below then apply PER COLUMN, which is
                   what lifts the int32 ceiling: widths with no
                   single-column plan (8-bit operands) become exactly
                   packable by spreading one dot product across several
                   int32 words at the cost of ``n_columns`` multiplies per
                   packed word position.
    """

    bits_a: int = 4
    bits_w: int = 4
    p: int = 11
    n_pairs: int = 4
    correction: str = "full"
    mr_bits: int = 0
    n_columns: int = 1

    def __post_init__(self) -> None:
        if self.correction not in CORRECTIONS:
            raise ValueError(
                f"bad correction {self.correction!r}; options: {CORRECTIONS}"
            )
        if self.bits_a < 1 or self.bits_w < 2:
            raise ValueError(
                f"operand widths too narrow: bits_a={self.bits_a} (min 1), "
                f"bits_w={self.bits_w} (min 2, signed)"
            )
        if self.n_pairs < 1 or self.p < 1:
            raise ValueError(f"n_pairs={self.n_pairs} and p={self.p} must be >= 1")
        if self.n_columns < 1 or self.n_columns > self.bits_a:
            raise ValueError(
                f"n_columns={self.n_columns} must be in [1, bits_a="
                f"{self.bits_a}]: every column carries at least one "
                "activation bit"
            )
        if (self.n_columns - 1) * self.col_bits_a >= self.bits_a:
            # e.g. 4 columns of ceil(6/4)=2-bit slices: the 4th slice is
            # provably zero — the same plan with 3 columns is strictly
            # cheaper, so the wasteful spelling is rejected outright
            canonical = -(-self.bits_a // self.col_bits_a)
            raise ValueError(
                f"n_columns={self.n_columns} leaves the last column with no "
                f"activation bits ({self.col_bits_a}-bit slices cover "
                f"bits_a={self.bits_a} with {canonical} columns); use "
                f"n_columns={canonical}"
            )
        if self.uses_mr and self.mr_bits < 1:
            raise ValueError(
                f"correction {self.correction!r} restores overlapped MSBs and "
                "needs mr_bits >= 1"
            )
        if not self.uses_mr and self.mr_bits:
            raise ValueError(
                f"mr_bits={self.mr_bits} is only meaningful with an mr "
                f"correction, not {self.correction!r}"
            )
        # int32 budget: |packed partial sum| must stay below 2**31, PER
        # COLUMN — each column only ever sees a ``col_bits_a``-bit slice of
        # the activation.  The three terms are the high / middle / low result
        # fields of one column's packed word after accumulating ``n_pairs``
        # products.
        max_a = (1 << self.col_bits_a) - 1
        max_w = 1 << (self.bits_w - 1)
        top = self.n_pairs * max_a * max_w * (1 << (2 * self.p))
        mid = self.n_pairs * 2 * max_a * max_w * (1 << self.p)
        low = self.n_pairs * max_a * max_w
        total = top + mid + low
        if total >= 1 << 31:
            per_col = " per column" if self.n_columns > 1 else ""
            raise ValueError(
                f"{self._describe()} overflows the int32 accumulator budget: "
                f"the accumulated packed sum spans {total.bit_length()} bits"
                f"{per_col} but the int32 accumulator provides 31 value bits; "
                f"reduce n_pairs (={self.n_pairs}), the field spacing p "
                f"(={self.p}), or raise n_columns (={self.n_columns}) "
                f"[certificate clause: {clauses.CLAUSE_INT32_ACCUMULATOR}]"
            )
        # The accumulated middle (dot-product) field must fit the bits the
        # extraction reads back: ``p`` for exact-spacing schemes,
        # ``p + mr_bits`` once the MSB restore widens the read.
        mid_mag = self.n_pairs * 2 * max_a * max_w
        if mid_mag >= 1 << (self.extract_width - 1):
            need = mid_mag.bit_length() + 1
            if self.uses_mr:
                raise ValueError(
                    f"{self._describe()} overflows the restored middle field: "
                    f"the accumulated dot product needs {need} bits but "
                    f"p + mr_bits = {self.extract_width}; raise p, raise "
                    f"mr_bits or reduce n_pairs "
                    f"[certificate clause: {clauses.CLAUSE_MIDDLE_FIELD}]"
                )
            raise ValueError(
                f"{self._describe()} overflows the middle field: the "
                f"accumulated dot product needs {need} bits but the field "
                f"spacing provides p = {self.p}; raise p, reduce n_pairs or "
                "use an mr correction "
                f"[certificate clause: {clauses.CLAUSE_MIDDLE_FIELD}]"
            )
        # Extraction aliasing: the sign-extension at ``extract_width`` reads
        # back M + g, where g is the low field's floor/rounding residue
        # (g = floor(L / 2^p), or the round-half-up variant).  The middle
        # field fitting is NOT enough — if the residue pushes the read-back
        # value past the signed extract width the sign bit flips and the
        # whole field wraps (error ~2^extract_width, far beyond the
        # advertised |g| bound).  Reachable for aggressive mr_bits, e.g.
        # a3w2 p=7 n_pairs=73 mr_bits=5 passes every check above.
        low_lo = -self.n_pairs * max_a * max_w
        low_hi = self.n_pairs * max_a * (max_w - 1)
        if self.rounds_half_up:
            g_lo = ((low_lo >> (self.p - 1)) + 1) >> 1
            g_hi = ((low_hi >> (self.p - 1)) + 1) >> 1
        else:
            g_lo, g_hi = low_lo >> self.p, low_hi >> self.p
        mid_hi = self.n_pairs * 2 * max_a * (max_w - 1)
        bound = 1 << (self.extract_width - 1)
        if -mid_mag + g_lo < -bound or mid_hi + g_hi > bound - 1:
            raise ValueError(
                f"{self._describe()} aliases under extraction: the dot field "
                f"plus the low-field residue spans "
                f"[{-mid_mag + g_lo}, {mid_hi + g_hi}] but sign-extension at "
                f"p + mr_bits = {self.extract_width} bits only represents "
                f"[{-bound}, {bound - 1}]; raise p or reduce mr_bits "
                f"[certificate clause: {clauses.CLAUSE_EXTRACTION_ALIAS}]"
            )

    def _describe(self) -> str:
        cols = f", n_columns={self.n_columns}" if self.n_columns > 1 else ""
        return (
            f"PackedDotSpec(a{self.bits_a}w{self.bits_w}, p={self.p}, "
            f"n_pairs={self.n_pairs}, {self.correction}{cols})"
        )

    @property
    def uses_mr(self) -> bool:
        return self.correction in ("mr", "mr+full")

    @property
    def rounds_half_up(self) -> bool:
        return self.correction in ("full", "mr+full")

    @property
    def chunk(self) -> int:
        """K elements consumed per extraction group (all columns together)."""
        return 2 * self.n_pairs

    @property
    def col_bits_a(self) -> int:
        """Activation bits per column slice (top slice may carry fewer)."""
        return -(-self.bits_a // self.n_columns)

    def column_shift(self, j: int) -> int:
        """Bit offset of column ``j``'s slice in the full activation — and
        therefore the recombination shift of its extracted dot field."""
        return j * self.col_bits_a

    @property
    def extract_width(self) -> int:
        return self.p + (self.mr_bits if self.uses_mr else 0)

    @property
    def delta(self) -> int:
        """Per-product padding in the paper's notation: spacing − result
        width (per column: a column's products are col_bits_a × bits_w)."""
        return self.p - (self.col_bits_a + self.bits_w)

    @property
    def provably_exact(self) -> bool:
        """Whether extraction is bit-exact for EVERY operand combination.

        ``full`` is exact by the legality checks (the middle field fits
        ``p`` and round-half-up absorbs the low-field borrow).  ``mr+full``
        is exact iff additionally the accumulated low field stays below
        ``2**(p-1)`` — then its spill into the squeezed middle field is
        fully absorbed by the rounding while the high-field contamination
        is subtracted exactly.  The biased schemes are never exact.  Column
        recombination preserves exactness: the slice identity
        ``a = Σ_j a_j · 2^(j·col_bits_a)`` is exact and the dot product is
        linear in the activation, so the recombined sum is exact whenever
        every column's extraction is."""
        if self.correction == "full":
            return True
        if self.correction == "mr+full":
            # exact iff round-half-up of the low field is identically zero:
            # L in [-n·amax·wmag, n·amax·(wmag-1)], and rhu(v) == 0 for
            # v in [-2^(p-1), 2^(p-1) - 1] — the lower bound is INCLUSIVE
            # (rhu(-2^(p-1)) = floor((-1+1)/2) = 0), hence <=.  The
            # analysis.verify interval walk derives the same boundary.
            max_a = (1 << self.col_bits_a) - 1
            max_w = 1 << (self.bits_w - 1)
            return self.n_pairs * max_a * max_w <= 1 << (self.p - 1)
        return False

    def name(self) -> str:
        """Stable human-readable plan id, e.g. ``a4w4-p10-n16-mr+full`` or
        ``a8w8-p11-n1-full-c4`` for a column-packed plan."""
        cols = f"-c{self.n_columns}" if self.n_columns > 1 else ""
        return (
            f"a{self.bits_a}w{self.bits_w}-p{self.p}-n{self.n_pairs}"
            f"-{self.correction}{cols}"
        )

    def density_vs_int8(self) -> float:
        """Multiplies saved vs one-multiply-per-product: each packed word
        computes 2 products, but every pair position costs ``n_columns``
        words."""
        return 2.0 / self.n_columns


# Optimal 32-bit-budget presets (derived in DESIGN.md §2 / EXPERIMENTS §Perf).
INT4_EXACT = PackedDotSpec(bits_a=4, bits_w=4, p=11, n_pairs=4, correction="full")
INT4_NAIVE = PackedDotSpec(bits_a=4, bits_w=4, p=11, n_pairs=4, correction="naive")
# Overpacked: spacing squeezed 13->10, 4x longer accumulation chains; the 3
# contaminated MSBs of the middle field are restored from exactly-computed
# LSBs of the high field (paper Eqns. 8/9 generalized to sums: products mod 8),
# plus round-half-up for the low-field borrow (beyond-paper combination).
INT4_MR_OVERPACKED = PackedDotSpec(
    bits_a=4, bits_w=4, p=10, n_pairs=16, correction="mr+full", mr_bits=3
)
INT2_EXACT = PackedDotSpec(bits_a=2, bits_w=2, p=10, n_pairs=32, correction="full")


def widen_for_shards(spec: PackedDotSpec, n_shards: int) -> PackedDotSpec:
    """The spec a ``n_shards``-way contraction-axis sharding must satisfy.

    Tensor-parallel row sharding reduces packed partial sums across devices
    IN WORD SPACE (psum of int32 packed words BEFORE field extraction — the
    same shifted-summation algebra as column recombination, stretched across
    the mesh).  The post-reduce word therefore accumulates
    ``n_shards * n_pairs`` products per extraction group, and every legality
    budget of :class:`PackedDotSpec` — the int32 accumulator ceiling, the
    middle-field width, extraction aliasing — must hold at THAT effective
    accumulation length, not the per-device one.

    Constructing the widened spec IS the legality check: an illegal sharding
    raises the constructor's certificate-clause-citing ``ValueError``
    (CLAUSE_INT32_ACCUMULATOR / CLAUSE_MIDDLE_FIELD /
    CLAUSE_EXTRACTION_ALIAS), exactly like an illegal ``n_pairs`` would.
    Extraction itself reads only ``p`` / ``extract_width`` / the correction
    — never ``n_pairs`` — so extracting the psummed word with the original
    spec is the same operation as extracting with the widened one; widening
    matters only for build-time legality and certification
    (see DESIGN.md §4).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards} must be >= 1")
    if n_shards == 1:
        return spec
    try:
        return dataclasses.replace(spec, n_pairs=n_shards * spec.n_pairs)
    except ValueError as e:
        raise ValueError(
            f"{spec.name()} cannot be row-sharded {n_shards} ways: the "
            f"cross-device word-space reduction accumulates "
            f"{n_shards}x{spec.n_pairs} products per extraction group and "
            f"the widened spec is illegal — {e}"
        ) from e


def _sext(v: jax.Array, width: int) -> jax.Array:
    mask = jnp.int32((1 << width) - 1)
    sign = jnp.int32(1 << (width - 1))
    return ((v & mask) ^ sign) - sign


def contamination_mask(spec: PackedDotSpec) -> int:
    """Bit mask of the high-field LSBs that corrupt an overpacked middle field."""
    return (1 << spec.mr_bits) - 1


def contamination_term(xa_chunk: jax.Array, ws_chunk: jax.Array,
                       spec: PackedDotSpec) -> jax.Array:
    """The high field's LSBs that leaked into the squeezed middle field.

    ``Σ a_odd·w_even mod 2**mr_bits`` over one extraction chunk, recomputed
    exactly from the operands (paper Eqns. 8/9 generalized to sums — only
    the low ``mr_bits`` of each operand can influence the result, so the
    masked dot is bit-exact and cheap).  Shared by the jnp reference and
    the Pallas kernel, like :func:`extract_accumulated_field`.

    ``xa_chunk``: (m, n_pairs, 2) paired activations;
    ``ws_chunk``: (n_pairs, 2, n) paired weights.
    """
    mask = jnp.int32(contamination_mask(spec))
    return jax.lax.dot_general(
        xa_chunk[:, :, 1] & mask,
        ws_chunk[:, 0, :] & mask,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & mask


def contamination_terms(xa: jax.Array, ws: jax.Array,
                        spec: PackedDotSpec) -> jax.Array:
    """Chunk-batched :func:`contamination_term`: every extraction group's
    contamination in ONE masked dot_general.

    ``xa``: (m, n_chunks, n_pairs, 2); ``ws``: (n_chunks, n_pairs, 2, n);
    returns (n_chunks, m, n).
    """
    mask = jnp.int32(contamination_mask(spec))
    return jax.lax.dot_general(
        xa[..., 1] & mask,        # (m, n_chunks, n_pairs)
        ws[..., 0, :] & mask,     # (n_chunks, n_pairs, n)
        (((2,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.int32,
    ) & mask


def extract_accumulated_field(
    partial: jax.Array, spec: PackedDotSpec, contam: jax.Array | None = None
) -> jax.Array:
    """Extract the accumulated middle (dot-product) field of a packed sum.

    This single helper IS the extraction semantics of the whole compute path:
    both ``ref_packed_matmul`` and the Pallas kernel call it, so the two are
    bit-identical by construction (and the parity matrix test re-verifies it
    empirically for every enumerated plan).

    ``contam`` — for mr corrections, the low ``mr_bits`` of the accumulated
    high field (``Σ a_odd·w_even mod 2**mr_bits``), recomputed exactly from
    the operands (paper Eqns. 8/9 generalized to sums) and subtracted after
    sign extension.
    """
    we = spec.extract_width
    if spec.rounds_half_up:
        t = ((partial >> (spec.p - 1)) + 1) >> 1
    else:  # naive floor extraction (arithmetic shift)
        t = partial >> spec.p
    e = _sext(t, we)
    if spec.uses_mr:
        if contam is None:
            raise ValueError("mr extraction needs the contamination term")
        e = _sext(e - (contam << (we - spec.mr_bits)), we)
    return e


def slice_column(x_u: jax.Array, spec: PackedDotSpec, j: int) -> jax.Array:
    """Column ``j``'s unsigned activation bit-slice (col_bits_a bits)."""
    if spec.n_columns == 1:
        return x_u.astype(jnp.int32)
    mask = jnp.int32((1 << spec.col_bits_a) - 1)
    return (x_u.astype(jnp.int32) >> spec.column_shift(j)) & mask


def _pad_k(x_u: jax.Array, w_s: jax.Array, mult: int):
    """Zero-pad the contraction axis to a multiple of ``mult``.

    Zero operand pairs contribute exactly zero in every correction scheme
    (packed words, extractions and contamination terms are all zero), so
    padding is bit-transparent."""
    k = x_u.shape[1]
    pad = (-k) % mult
    if pad:
        x_u = jnp.pad(x_u, ((0, 0), (0, pad)))
        w_s = jnp.pad(w_s, ((0, pad), (0, 0)))
    return x_u, w_s


class PackedWeightWords(NamedTuple):
    """Weights packed ONCE for reuse across many packed matmuls.

    ``words``: (n_chunks, n_pairs, n) int32 — each pair's packed word
    ``w_even + (w_odd << p)`` grouped into extraction chunks.
    ``wsc``: (n_chunks, n_pairs, 2, n) int32 contamination operands, built
    ONLY for mr corrections (the masked high-field dot needs the raw paired
    weights); ``None`` for exact-spacing plans — non-mr plans pay no
    reshape/traffic for an operand stream they never read.
    """

    words: jax.Array
    wsc: jax.Array | None

    @property
    def k(self) -> int:
        """Contraction length the words cover (a multiple of the chunk)."""
        return self.words.shape[-3] * 2 * self.words.shape[-2]


def pack_weight_words(w_s: jax.Array, spec: PackedDotSpec) -> PackedWeightWords:
    """The PACK stage of the packed matmul: (k, n) signed ints → reusable
    :class:`PackedWeightWords`.  Ragged ``k`` is zero-padded to a whole
    number of extraction chunks (bit-transparent — see :func:`_pad_k`).

    This is the "pack once" half of the paper's economics: operands are
    packed a single time (at quantize/engine-build time in serving) and the
    words are reused by every subsequent matmul, instead of being rebuilt
    from the stored integers on every K-step of every call.
    """
    k, n = w_s.shape
    pad = (-k) % spec.chunk
    if pad:
        w_s = jnp.pad(w_s, ((0, pad), (0, 0)))
        k += pad
    n_chunks = k // spec.chunk
    ws = w_s.astype(jnp.int32).reshape(k // 2, 2, n)
    words = (ws[:, 1, :] + (ws[:, 0, :] << spec.p)).reshape(
        n_chunks, spec.n_pairs, n
    )
    wsc = ws.reshape(n_chunks, spec.n_pairs, 2, n) if spec.uses_mr else None
    return PackedWeightWords(words, wsc)


def packed_tile_matmul_prepacked(
    x_u: jax.Array,
    words: jax.Array,
    wsc: jax.Array | None,
    spec: PackedDotSpec,
) -> jax.Array:
    """The COMPUTE stage: already-packed weight words × unsigned activations.

    The jnp reference of the Pallas kernel (``kernels.packed_matmul``),
    which computes the same wrapping int32 chunk sums on the VPU and shares
    :func:`extract_accumulated_field`, so the two are bit-identical.
    ``x_u``: (m, k) with ``k = n_chunks * spec.chunk``;
    ``words``/``wsc`` as produced by :func:`pack_weight_words`.

    Per column: pack the activation slice's pair words, contract ALL
    extraction groups in one chunk-batched dot_general (n_pairs wide
    multiply-accumulates per packed word — no per-chunk python unroll, so
    n_pairs=1 column plans like a8w8 don't explode into hundreds of rank-1
    dots), extract every group's middle field, sum the fields (int32
    addition is associative mod 2**32) and recombine at the slice offset.
    Multi-column plans reuse the SAME packed weight words for every stream.
    """
    m, k = x_u.shape
    n_chunks, n_pairs, n = words.shape
    if spec.uses_mr and wsc is None:
        raise ValueError(
            f"{spec.name()} is an mr plan: the prepacked compute stage needs "
            "the contamination operands (pack_weight_words builds them)"
        )
    acc = jnp.zeros((m, n), dtype=jnp.int32)
    for j in range(spec.n_columns):
        xa = slice_column(x_u, spec, j).reshape(m, k // 2, 2)
        a_words = (xa[:, :, 0] + (xa[:, :, 1] << spec.p)).reshape(
            m, n_chunks, spec.n_pairs
        )
        partial = jax.lax.dot_general(   # (n_chunks, m, n), batched chunks
            a_words,
            words,
            (((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.int32,
        )
        contam = (
            contamination_terms(
                xa.reshape(m, n_chunks, spec.n_pairs, 2), wsc, spec
            )
            if spec.uses_mr else None
        )
        field = extract_accumulated_field(partial, spec, contam)
        col = jnp.sum(field, axis=0)
        shift = spec.column_shift(j)
        acc = acc + (col << shift if shift else col)
    return acc


def packed_tile_matmul(x_u: jax.Array, w_s: jax.Array,
                       spec: PackedDotSpec) -> jax.Array:
    """Pack + compute in one call (the per-call path): (m, k) unsigned ×
    (k, n) signed → (m, n) int32, ``k`` a multiple of ``spec.chunk``.

    Kept for callers whose weights change every call (training-style
    use); serving packs once via
    :func:`pack_weight_words` and runs only the compute stage per step.
    """
    packed = pack_weight_words(w_s, spec)
    return packed_tile_matmul_prepacked(x_u, packed.words, packed.wsc, spec)


def ref_packed_matmul(
    x_u: jax.Array, w_s: jax.Array, spec: PackedDotSpec = INT4_EXACT
) -> jax.Array:
    """Bit-accurate jnp mirror of the pair-packed Pallas kernel.

    ``x_u``: (M, K) unsigned ints (0..2^bits_a-1) stored in any int dtype.
    ``w_s``: (K, N) signed ints.  Ragged K is zero-padded to ``spec.chunk``.
    Returns int32 (M, N).

    Multi-column plans (``spec.n_columns > 1``) run one packed-word stream
    per activation bit-slice against the SAME packed weights and recombine
    each extracted dot field shifted by its slice offset — all in wrapping
    int32 arithmetic, so kernel/ref/simulator stay bit-identical even where
    a (caller-side) output overflow wraps.  The compute itself lives in
    :func:`packed_tile_matmul`.
    """
    x_u, w_s = _pad_k(x_u, w_s, spec.chunk)
    return packed_tile_matmul(x_u, w_s, spec)


def ref_packed_matmul_prepacked(
    x_u: jax.Array,
    packed: PackedWeightWords,
    spec: PackedDotSpec = INT4_EXACT,
) -> jax.Array:
    """jnp prepacked matmul: consume :func:`pack_weight_words` output.

    Bit-identical to ``ref_packed_matmul(x_u, w_s, spec)`` for the weights
    the words were packed from (the compute stage is shared code); ``x_u``'s
    K is zero-padded up to the words' chunk grid."""
    k = x_u.shape[1]
    pad = packed.k - k
    if pad < 0:
        raise ValueError(
            f"activation K={k} exceeds the packed weights' K={packed.k}"
        )
    if pad:
        x_u = jnp.pad(x_u, ((0, 0), (0, pad)))
    return packed_tile_matmul_prepacked(x_u, packed.words, packed.wsc, spec)


def exact_int_matmul_fits_f32(k: int, max_a: int, max_w: int) -> bool:
    """Whether an integer matmul with |a| <= max_a, |w| <= max_w over a
    K-long contraction is EXACT when evaluated in f32.

    Every partial sum is an integer of magnitude <= k * max_a * max_w; f32
    represents all integers up to 2**24 exactly, so as long as that bound
    fits, an f32 GEMM (which hits the fast dense path on CPU/GPU backends
    where int dots lower to scalar loops) returns bit-identical integers to
    the int32 dot.  The serving fast path uses this to run *exact* packed
    plans through the float unit without changing a single output bit.
    """
    return k * max_a * max_w < 1 << 24


def ref_quantized_matmul(x_u: jax.Array, w_s: jax.Array) -> jax.Array:
    """The mathematically exact unsigned×signed integer matmul (int32)."""
    return jax.lax.dot_general(
        x_u.astype(jnp.int32),
        w_s.astype(jnp.int32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


# ---- packed-storage int4 (production path) ------------------------------


def pack_int4_weights(w_s: np.ndarray | jax.Array) -> jax.Array:
    """(K, N) int4 values -> (K//2, N) uint8, two nibbles per byte."""
    w = jnp.asarray(w_s, dtype=jnp.int8)
    k = w.shape[0]
    if k % 2:
        raise ValueError("K must be even to pack nibbles")
    lo = w[0::2] & 0xF
    hi = w[1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4_weights(packed: jax.Array) -> jax.Array:
    """(K//2, N) uint8 -> (K, N) int8 with sign-extended nibbles."""
    b = packed.astype(jnp.int8)
    lo = (b << 4) >> 4  # arithmetic shift sign-extends the low nibble
    hi = b >> 4
    k2, n = packed.shape
    out = jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)
    return out


def ref_int4_matmul(x_q: jax.Array, w_packed: jax.Array) -> jax.Array:
    """Oracle for the production kernel: unpack then exact int8 matmul
    with int32 accumulation (``x_q``: int8, as ``quantize_signed`` makes)."""
    return jax.lax.dot_general(
        x_q,
        unpack_int4_weights(w_packed),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
