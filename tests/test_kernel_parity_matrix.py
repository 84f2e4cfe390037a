"""Exhaustive kernel-parity matrix: the Pallas kernel is bit-exact vs the
``core.packing``/``core.correction``-validated ground truth for EVERY plan
the enumerator emits — all schemes (naive/full/mr/mr+full), all operand
widths (2/4/6/8 bit), all multi-DSP column counts, non-default and ragged
block/problem shapes.

Three layers of assurance, replacing the old single-spec spot checks:

1. every emitted plan: kernel == jnp ref == ``core.packing``-based DSP
   simulator (``tests/dsp_sim.py``), bit-for-bit, on a ragged shape — a
   genuine three-way cross-check since the simulator shares no packing or
   extraction code with the kernel/ref pair;
2. exactness where the plan algebra promises it: every ``full`` plan equals
   the mathematically exact integer matmul (including the column-packed
   a8w8 plans that lift the int32 ceiling); every ``naive`` plan is biased
   by at most −1 per extraction per column (scaled by the column's
   recombination shift); every mr plan's error is bounded;
3. block-shape sweep: representative plans per scheme — and column-packed
   representatives — across non-default and ragged (M, K, N) grids,
   including blocks larger than the problem.

Plus the plan-construction failure surface: requesting an (n_pairs, δ,
n_columns) combination that overflows the int32 accumulator (or a field)
fails AT CONSTRUCTION with an error naming the violated budget — never
deep in the kernel.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from dsp_sim import simulate_packed_matmul

from repro.core.quantize import quantize_unsigned
from repro.kernels import ref
from repro.kernels.packed_matmul import (
    DEFAULT_BLOCK,
    default_block_for,
    packed_matmul,
    packed_matmul_prepacked,
)
from repro.kernels.ref import CORRECTIONS, PackedDotSpec
from repro.tuning import enumerate_specs

RNG = np.random.default_rng(20)

WIDTH_PAIRS = ((2, 2), (4, 4), (6, 6), (8, 8))
ALL_SPECS = [s for a, w in WIDTH_PAIRS for s in enumerate_specs(a, w)]


def _operands(m, k, n, spec):
    x = RNG.integers(0, 1 << spec.bits_a, (m, k)).astype(np.int32)
    w = RNG.integers(
        -(1 << (spec.bits_w - 1)), 1 << (spec.bits_w - 1), (k, n)
    ).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(w)


def _column_scale(spec):
    """Worst-case recombination multiplier of one unit of per-column
    extraction error: Σ_j 2^(j·col_bits_a)."""
    return sum(1 << spec.column_shift(j) for j in range(spec.n_columns))


def _assert_parity(spec, shape, block, simulator=True):
    m, k, n = shape
    x, w = _operands(m, k, n, spec)
    got = packed_matmul(x, w, spec=spec, block=block, interpret=True)
    want = ref.ref_packed_matmul(x, w, spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if simulator:
        sim = simulate_packed_matmul(spec, np.asarray(x), np.asarray(w))
        np.testing.assert_array_equal(sim, np.asarray(got))
    return np.asarray(got), x, w


class TestEveryEmittedPlan:
    """Acceptance gate: parity holds for every plan the enumerator emits."""

    def test_enumerator_emits_plans_for_every_width(self):
        for a_bits, w_bits in WIDTH_PAIRS:
            assert enumerate_specs(a_bits, w_bits), (a_bits, w_bits)

    def test_a8w8_needs_columns_and_has_provably_exact_plans(self):
        # single-word packing still admits NO 8-bit plan inside int32 …
        assert enumerate_specs(8, 8, n_columns_choices=(1,)) == ()
        # … and the column axis is exactly what lifts that ceiling
        a8 = enumerate_specs(8, 8)
        assert a8 and all(s.n_columns > 1 for s in a8)
        exact = [s for s in a8 if s.provably_exact]
        assert exact, "a8w8 must have at least one provably exact column plan"

    @pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name())
    def test_kernel_bit_equals_ground_truth(self, spec):
        # ragged K exercises the zero-pad path; block bk = one chunk group
        shape = (8, 2 * spec.chunk + 3, 16)
        got, x, w = _assert_parity(spec, shape, (8, 16, spec.chunk))
        exact = np.asarray(ref.ref_quantized_matmul(x, w))
        err = got - exact
        n_extractions = -(-shape[1] // spec.chunk)
        scale = _column_scale(spec)
        if spec.correction == "full":
            np.testing.assert_array_equal(got, exact)
        elif spec.correction == "naive":
            # the white-paper bias: at most -1 per extraction per column
            # (column j's bias recombines scaled by 2^(j·col_bits_a)),
            # never positive
            assert err.max() <= 0 and err.min() >= -n_extractions * scale
        else:  # mr corrections: restored error is bounded per extraction by
            # the low-field spill into the squeezed middle field, again
            # scaled by the column recombination
            bound = n_extractions * (1 << spec.mr_bits) * scale
            assert np.abs(err).max() <= bound, spec.name()


class TestBlockShapeMatrix:
    """Scheme × block × ragged-problem grid for representative plans."""

    REPRESENTATIVE = {
        "naive": PackedDotSpec(4, 4, 11, 4, "naive"),
        "full": PackedDotSpec(4, 4, 11, 4, "full"),
        "mr": PackedDotSpec(4, 4, 10, 16, "mr", 3),
        "mr+full": PackedDotSpec(4, 4, 10, 16, "mr+full", 3),
    }
    # Column-packed representatives: the high-n_pairs exact a4w4 plan and
    # the a8w8 plan that exists ONLY thanks to columns.
    COLUMN_REPRESENTATIVE = [
        PackedDotSpec(4, 4, 11, 16, "full", n_columns=2),
        PackedDotSpec(8, 8, 11, 1, "full", n_columns=4),
        PackedDotSpec(8, 8, 10, 1, "mr+full", 1, n_columns=4),
    ]

    @pytest.mark.parametrize("scheme", CORRECTIONS)
    @pytest.mark.parametrize(
        "block", [(128, 128, 128), (32, 64, 128), (16, 16, 64)]
    )
    @pytest.mark.parametrize(
        "shape", [(128, 128, 128), (96, 200, 72), (33, 130, 17)]
    )
    def test_parity_across_blocks_and_ragged_shapes(self, scheme, block, shape):
        _assert_parity(self.REPRESENTATIVE[scheme], shape, block)

    @pytest.mark.parametrize(
        "spec", COLUMN_REPRESENTATIVE, ids=lambda s: s.name()
    )
    @pytest.mark.parametrize("block", [(32, 64, 128), (16, 16, 64)])
    @pytest.mark.parametrize("shape", [(96, 200, 72), (33, 130, 17)])
    def test_column_parity_across_blocks_and_ragged_shapes(
        self, spec, block, shape
    ):
        """Three-way parity for column-packed plans on ragged grids."""
        _assert_parity(spec, shape, block)

    def test_block_larger_than_problem(self):
        _assert_parity(self.REPRESENTATIVE["full"], (8, 24, 8), (128, 128, 128))

    def test_bk_not_multiple_of_chunk_rejected(self):
        spec = self.REPRESENTATIVE["mr"]  # chunk 32
        x, w = _operands(8, 64, 8, spec)
        with pytest.raises(ValueError, match="multiple of spec.chunk"):
            packed_matmul(x, w, spec=spec, block=(8, 8, 48), interpret=True)


class TestPrepackedParity:
    """The prepacked fast path is bit-identical to the per-call kernel for
    EVERY emitted plan: ``packed_matmul_prepacked(pack_weight_words(w)) ==
    packed_matmul(w) == ref == simulator`` — packing weights once at engine
    build must never change a single output bit."""

    @pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name())
    def test_prepacked_bit_equals_per_call(self, spec):
        shape = (8, 2 * spec.chunk + 3, 16)
        m, k, n = shape
        x, w = _operands(m, k, n, spec)
        want = np.asarray(ref.ref_packed_matmul(x, w, spec))
        packed = ref.pack_weight_words(w, spec)
        # the wsc contamination stream is materialized ONLY for mr plans
        assert (packed.wsc is None) == (not spec.uses_mr)
        got_ref = np.asarray(ref.ref_packed_matmul_prepacked(x, packed, spec))
        got_kernel = np.asarray(packed_matmul_prepacked(
            x, packed.words, packed.wsc, spec=spec,
            block=(8, 16, spec.chunk), interpret=True,
        ))
        np.testing.assert_array_equal(got_ref, want)
        np.testing.assert_array_equal(got_kernel, want)

    @pytest.mark.parametrize(
        "spec",
        [
            PackedDotSpec(4, 4, 11, 4, "full"),
            PackedDotSpec(4, 4, 10, 16, "mr+full", 3),
            PackedDotSpec(8, 8, 11, 1, "full", n_columns=4),
        ],
        ids=lambda s: s.name(),
    )
    def test_prepacked_three_way_with_simulator(self, spec):
        m, k, n = 5, 3 * spec.chunk, 12
        x, w = _operands(m, k, n, spec)
        packed = ref.pack_weight_words(w, spec)
        got = np.asarray(packed_matmul_prepacked(
            x, packed.words, packed.wsc, spec=spec, interpret=True,
        ))
        sim = simulate_packed_matmul(spec, np.asarray(x), np.asarray(w))
        np.testing.assert_array_equal(got, sim)

    @pytest.mark.parametrize(
        "spec",
        [
            PackedDotSpec(4, 4, 11, 4, "full"),
            PackedDotSpec(4, 4, 10, 16, "mr+full", 3),
            PackedDotSpec(8, 8, 11, 1, "full", n_columns=4),
        ],
        ids=lambda s: s.name(),
    )
    def test_fused_quantize_prologue_matches_staged(self, spec):
        """The in-kernel activation quantize (f32 tile + row scale) equals
        quantize-then-call bit for bit — no HBM staging round-trip.  The
        weights' K exceeds the activation's, so the padded tail must stay
        transparent too."""
        rng = np.random.default_rng(7)
        m, k, n = 5, 2 * spec.chunk + 3, 12
        w = jnp.asarray(rng.integers(
            -(1 << (spec.bits_w - 1)), 1 << (spec.bits_w - 1), (k + 5, n)
        ), jnp.int32)
        xf = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        xq = quantize_unsigned(xf, bits=spec.bits_a, axis=-1)
        packed = ref.pack_weight_words(w, spec)
        staged = np.asarray(packed_matmul_prepacked(
            jnp.asarray(xq.values, jnp.int32), packed.words, packed.wsc,
            spec=spec, interpret=True,
        ))
        fused = np.asarray(packed_matmul_prepacked(
            xf, packed.words, packed.wsc, spec=spec, interpret=True,
            x_scale=xq.scale, x_zp=xq.zero_point,
        ))
        np.testing.assert_array_equal(fused, staged)

    def test_decode_default_block_is_small_m(self):
        spec = PackedDotSpec(4, 4, 11, 4, "full")
        assert default_block_for(2, spec)[0] == 8
        assert default_block_for(128, spec)[0] == DEFAULT_BLOCK[0] > 8
        # chunk-aligned bk even for long-chunk plans, and always 128-lane
        # activation halves (what the chip's compiler requires)
        for s in (spec, PackedDotSpec(4, 4, 10, 16, "mr+full", 3),
                  PackedDotSpec(2, 2, 10, 3, "full")):  # chunks 8, 32, 6
            for m in (2, 128):
                bk = default_block_for(m, s)[2]
                assert bk % s.chunk == 0 and (bk // 2) % 128 == 0

    def test_activation_shorter_than_packed_weights(self):
        """An x truncated well below the words' K — with a bk that does
        not divide the words' grid — must still cover every weight chunk
        (regression: the K grid used to truncate tail chunks here)."""
        spec = PackedDotSpec(4, 4, 11, 4, "full")  # chunk 8
        rng = np.random.default_rng(11)
        k_w, k_x, n = 40, 19, 12
        w = jnp.asarray(rng.integers(-8, 8, (k_w, n)), jnp.int32)
        x = jnp.asarray(rng.integers(0, 16, (3, k_x)), jnp.int32)
        packed = ref.pack_weight_words(w, spec)
        want = np.asarray(ref.ref_packed_matmul(
            jnp.pad(x, ((0, 0), (0, k_w - k_x))), w, spec
        ))
        for block in ((8, 16, 16), (8, 16, 8), (8, 16, 48)):
            got = np.asarray(packed_matmul_prepacked(
                x, packed.words, packed.wsc, spec=spec, block=block,
                interpret=True,
            ))
            np.testing.assert_array_equal(got, want)

    def test_mr_plan_requires_contamination_operands(self):
        spec = PackedDotSpec(4, 4, 10, 16, "mr+full", 3)
        x, w = _operands(4, spec.chunk, 8, spec)
        packed = ref.pack_weight_words(w, spec)
        with pytest.raises(ValueError, match="contamination"):
            packed_matmul_prepacked(
                x, packed.words, None, spec=spec, interpret=True
            )


class TestConstructionTimeBudgets:
    """Satellite: overflowing (n_pairs, δ) combinations fail at plan
    construction with errors naming the violated budget."""

    def test_int32_accumulator_budget_named(self):
        with pytest.raises(ValueError, match="int32 accumulator budget"):
            PackedDotSpec(bits_a=4, bits_w=4, p=12, n_pairs=8)

    def test_int32_budget_message_names_the_knobs(self):
        with pytest.raises(ValueError, match=r"n_pairs \(=8\).*p \(=12\)"):
            PackedDotSpec(bits_a=4, bits_w=4, p=12, n_pairs=8)

    def test_middle_field_budget_named(self):
        with pytest.raises(
            ValueError, match="middle field.*p = 9.*mr correction"
        ):
            PackedDotSpec(bits_a=4, bits_w=4, p=9, n_pairs=4, correction="full")

    def test_restored_middle_field_budget_named(self):
        # even with the mr widening, n_pairs=64 at p=5 cannot hold the sum
        with pytest.raises(ValueError, match="restored middle field"):
            PackedDotSpec(4, 4, p=5, n_pairs=64, correction="mr", mr_bits=1)

    def test_int8_has_no_legal_single_column_plan_and_says_why(self):
        with pytest.raises(ValueError, match="raise n_columns"):
            PackedDotSpec(bits_a=8, bits_w=8, p=17, n_pairs=1, correction="full")
        # the very combination the error suggests is legal — and exact
        spec = PackedDotSpec(8, 8, p=11, n_pairs=1, correction="full",
                             n_columns=4)
        assert spec.provably_exact

    def test_n_columns_validated_at_construction(self):
        with pytest.raises(ValueError, match="n_columns=0"):
            PackedDotSpec(4, 4, 11, 4, n_columns=0)
        with pytest.raises(ValueError, match="at least one activation bit"):
            PackedDotSpec(4, 4, 11, 4, n_columns=5)

    def test_per_column_budget_named_in_error(self):
        # 2 columns of 4-bit slices are NOT enough for a8w8 at n_pairs=8
        with pytest.raises(ValueError, match="per column"):
            PackedDotSpec(8, 8, p=17, n_pairs=8, correction="full",
                          n_columns=2)

    def test_mr_bits_consistency_enforced(self):
        with pytest.raises(ValueError, match="mr_bits >= 1"):
            PackedDotSpec(4, 4, 10, 4, correction="mr", mr_bits=0)
        with pytest.raises(ValueError, match="only meaningful"):
            PackedDotSpec(4, 4, 11, 4, correction="full", mr_bits=2)

    def test_every_emitted_plan_constructs_and_names_itself(self):
        names = [s.name() for s in ALL_SPECS]
        assert len(set(names)) == len(names)  # enumeration has no duplicates
        for spec, name in zip(ALL_SPECS, names):
            assert f"n{spec.n_pairs}" in name and spec.correction in name
