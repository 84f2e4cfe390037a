"""Family-parameterized serving conformance suite.

The spine of the "serve every registry family" claim: one representative
smoke config per architecture family runs through BOTH engines (fixed-slot
``Engine`` over dense per-slot cache windows, ``ContinuousEngine`` over the
paged pool) in every serving quant mode, and greedy decode must be
token-for-token identical across the engine/cache pair.  Full-context
forwards are NOT the reference for MoE-bearing families — expert capacity
``ceil(T·k/E·cf)`` depends on the static batch token count, so incremental
decode legitimately diverges from a monolithic forward; the serving
invariant is cross-engine identity, plus reference equality where the
math allows it (non-MoE families).

Recurrent regressions ride along:

* chunked prefill == chunk-1 prefill **bit-for-bit** for ssm/hybrid (the
  lifted fallback): the engines pass a per-row valid-length mask and the
  recurrent mixers advance state through a strictly sequential per-token
  scan of the exact chunk math, so the decode state after a C-token chunk
  equals C single-token steps — property-tested over prompt lengths and
  chunk sizes, including the raw cache arrays.
* staggered prefill-join and preemption + bit-identical resume on
  recurrent state (ssm white-box via ``_preempt`` — an ssm lane holds no
  pages, so page pressure never evicts it organically; hybrid organically
  under a tight pool).
* shared-prefix guards: the one remaining family exclusion must name the
  exact blocking feature in its error.

Fast lane runs one SSM and one MoE representative at native/int4_packed;
the full (family x mode) matrix and the tuned/mixed columns are slow-lane.
"""

import dataclasses
import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, integers, sampled_from
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serving import ContinuousEngine, Engine, ServeConfig

# one representative smoke config per serving-relevant family axis
# (h2o rides along for the sliding-window attention variant of dense —
# its ring cache is a distinct serving code path)
FAMILY_ARCHS = (
    "qwen1.5-110b",           # dense
    "h2o-danube-3-4b",        # dense + sliding window
    "moonshot-v1-16b-a3b",    # moe
    "xlstm-1.3b",             # ssm
    "jamba-v0.1-52b",         # hybrid (mamba + attn + moe)
    "whisper-large-v3",       # encdec decoder
    "llava-next-mistral-7b",  # vlm
)
FAST_ARCHS = ("xlstm-1.3b", "moonshot-v1-16b-a3b")
MODES = ("native", "int4_packed", "dsp_tuned", "dsp_mixed")
FAST_MODES = ("native", "int4_packed")
# shrunken sensitivity pass for the dsp_mixed column (the eager probe
# forwards dominate; two widths and a few calib tokens pin the plumbing)
MIXED_KW = dict(width_candidates=((4, 4), (8, 8)), calib_tokens=8)

MAX_LEN = 32
PROMPT = list(range(5, 14))
N_NEW = 6


@pytest.fixture(autouse=True)
def _drop_compiled_executables():
    """XLA:CPU's JIT segfaults once one process holds too many compiled
    executables (tests/conftest.py).  Every test here compiles whole
    engines, and the dsp_mixed sensitivity pass one eager program per
    probe, so the process would reach that limit within this file:
    release them before each test."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


@functools.lru_cache(maxsize=None)
def _model(arch):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


@functools.lru_cache(maxsize=None)
def _mixed_allocation(arch):
    """One sensitivity pass per arch, shared by both engines' builds."""
    from repro.tuning import allocate_mixed_plans, measure_layer_sensitivity

    cfg, params = _model(arch)
    cfg_q = dataclasses.replace(
        cfg, quant=dataclasses.replace(cfg.quant, mode="dsp_tuned")
    )
    sens = measure_layer_sensitivity(
        params, cfg_q, widths=MIXED_KW["width_candidates"],
        n_calib_tokens=MIXED_KW["calib_tokens"],
    )
    return allocate_mixed_plans(sens, widths=MIXED_KW["width_candidates"])


def _engines(arch, quant, chunk=4, slots=2, **kw):
    cfg, params = _model(arch)
    base = dict(n_slots=slots, max_len=MAX_LEN, prefill_chunk=chunk,
                quant_mode=quant, **kw)
    if quant == "dsp_mixed":
        base.update(MIXED_KW)
        mixed = {"mixed_allocation": _mixed_allocation(arch)}
    else:
        mixed = {}
    fifo = Engine(cfg, params, ServeConfig(**base), **mixed)
    cont = ContinuousEngine(
        cfg, params, ServeConfig(page_size=8, **base), **mixed
    )
    return fifo, cont


def _gen_one(eng, prompt, max_new):
    """Single-prompt generate on a possibly reused (lru-cached) engine:
    outputs are keyed by request id, which advances across reuses, so
    ``[0]`` only works on a fresh engine."""
    (toks,) = eng.generate([list(prompt)], max_new=max_new).values()
    return toks


def _matrix_params():
    out = []
    for arch in FAMILY_ARCHS:
        for mode in MODES:
            fast = arch in FAST_ARCHS and mode in FAST_MODES
            marks = () if fast else (pytest.mark.slow,)
            out.append(pytest.param(arch, mode, marks=marks,
                                    id=f"{arch}-{mode}"))
    return out


@pytest.mark.parametrize("arch,quant", _matrix_params())
def test_cross_engine_token_identity(arch, quant):
    """Every (family, quant mode): greedy decode through the dense-cache
    FIFO engine equals the paged continuous engine token-for-token."""
    fifo, cont = _engines(arch, quant)
    a = fifo.generate([list(PROMPT)], max_new=N_NEW)[0]
    b = cont.generate([list(PROMPT)], max_new=N_NEW)[0]
    assert len(a) == N_NEW
    assert a == b, f"{arch}/{quant}: fifo {a} != continuous {b}"


@pytest.mark.parametrize(
    "arch", ["qwen1.5-110b", "h2o-danube-3-4b", "xlstm-1.3b",
             "llava-next-mistral-7b"]
)
@pytest.mark.slow
def test_engines_match_full_context_reference(arch):
    """Non-MoE decoder-only families: both engines also equal the greedy
    full-context forward.  MoE capacity is batch-shape-dependent and
    whisper's decoder serves with chunk-local cross-attention (no encoder
    features in token-only serving, so ``kv_x=None`` degrades xattn to
    uncached non-causal self-attention over the current chunk — a
    monolithic forward attends over the whole sequence instead), so those
    families are pinned by cross-engine identity only."""
    cfg, params = _model(arch)
    seq, want = list(PROMPT), []
    for _ in range(N_NEW):
        logits, _, _ = T.forward(params, cfg, jnp.asarray([seq], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        seq.append(nxt)
    fifo, cont = _engines(arch, "native")
    assert fifo.generate([list(PROMPT)], max_new=N_NEW)[0] == want
    assert cont.generate([list(PROMPT)], max_new=N_NEW)[0] == want


def test_swa_ring_wraparound_cross_engine():
    """Sliding-window prompts longer than the window: the paged ring
    (slot = pos % window) must match the dense per-slot ring."""
    cfg, params = _model("h2o-danube-3-4b")
    assert cfg.sliding_window and cfg.sliding_window < 64
    prompt = list(range(5, 5 + cfg.sliding_window + 8))  # wraps the ring
    base = dict(n_slots=2, max_len=64, prefill_chunk=4, quant_mode="native")
    a = Engine(cfg, params, ServeConfig(**base)).generate(
        [list(prompt)], max_new=6)[0]
    b = ContinuousEngine(cfg, params, ServeConfig(page_size=8, **base)
                         ).generate([list(prompt)], max_new=6)[0]
    assert a == b


# ---- chunked prefill == chunk-1 prefill (the lifted fallback) -----------


@functools.lru_cache(maxsize=None)
def _chunk_engine(arch, chunk):
    cfg, params = _model(arch)
    return Engine(cfg, params, ServeConfig(
        n_slots=2, max_len=MAX_LEN, prefill_chunk=chunk, quant_mode="native"
    ))


def _prompt_from_seed(cfg, seed, length):
    rng = np.random.default_rng(seed)
    return [int(v) for v in rng.integers(2, cfg.vocab_size, size=length)]


@pytest.mark.parametrize("arch,state_atol", [
    ("xlstm-1.3b", 0.0),        # bitwise, even through XLA fusion
    ("jamba-v0.1-52b", 1e-5),   # ulp-level fusion drift (see docstring)
])
def test_chunked_prefill_matches_chunk1_state(arch, state_atol):
    """ssm/hybrid fixed-case regression: chunked prefill emits the same
    greedy tokens as chunk-1 prefill, and the recurrent decode state
    matches — bitwise for ssm; for hybrid within a few ulp, because XLA
    fuses the l=C and l=1 forward programs differently around mamba's
    exp/softplus chains (the mixer math itself is bit-exact per chunk
    size — ``mamba()`` called standalone matches bitwise — so the
    tolerance covers compiled-program fusion only, not the algorithm)."""
    cfg, _ = _model(arch)
    prompt = _prompt_from_seed(cfg, 7, 13)
    ref_eng = _chunk_engine(arch, 1)
    ref = _gen_one(ref_eng, prompt, 4)
    ref_cache = jax.tree.map(np.asarray, ref_eng.cache)
    for chunk in (4, 7, 16):
        eng = _chunk_engine(arch, chunk)
        got = _gen_one(eng, prompt, 4)
        assert got == ref, f"chunk={chunk}: {got} != {ref}"
        got_cache = jax.tree.map(np.asarray, eng.cache)
        flat_ref = jax.tree_util.tree_flatten_with_path(ref_cache)[0]
        flat_got = jax.tree.leaves(got_cache)
        for (path, r), g in zip(flat_ref, flat_got):
            # the dense attention window is max_len + chunk - 1 wide, so
            # KV leaves gain chunk-1 trailing slots — compare the common
            # position prefix (prompt + decode all land below max_len here)
            if r.shape != g.shape:
                (ax,) = [i for i, (a, b) in
                         enumerate(zip(r.shape, g.shape)) if a != b]
                n = min(r.shape[ax], g.shape[ax])
                r = np.take(r, np.arange(n), axis=ax)
                g = np.take(g, np.arange(n), axis=ax)
            ok = (np.array_equal(r, g) if state_atol == 0.0
                  else np.allclose(r, g, rtol=0, atol=state_atol))
            assert ok, (
                f"chunk={chunk}: decode state differs at "
                f"{jax.tree_util.keystr(path)}"
            )


@pytest.mark.slow
@given(arch=sampled_from(["xlstm-1.3b", "jamba-v0.1-52b"]),
       length=integers(2, 24),
       chunk=sampled_from([2, 3, 4, 5, 8, 16]),
       seed=integers(0, 2**31))
def test_chunked_prefill_matches_chunk1_property(arch, length, chunk, seed):
    """Property form over prompt length x chunk size x content: the
    recurrent-state chunking invariant holds for arbitrary prompts, not a
    blessed case (engines are cached per chunk size, so each case is two
    generate calls, not two rebuilds)."""
    cfg, _ = _model(arch)
    prompt = _prompt_from_seed(cfg, seed, length)
    ref = _gen_one(_chunk_engine(arch, 1), prompt, 3)
    got = _gen_one(_chunk_engine(arch, chunk), prompt, 3)
    assert got == ref


@pytest.mark.parametrize("arch", ["xlstm-1.3b", "jamba-v0.1-52b"])
def test_continuous_chunked_prefill_matches_chunk1(arch):
    """The continuous engine honors the same invariant (its prefill path
    masks and merges differently from the FIFO engine's)."""
    cfg, params = _model(arch)
    prompt = _prompt_from_seed(cfg, 11, 13)
    outs = {}
    for chunk in (1, 4, 16):
        eng = ContinuousEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=MAX_LEN, prefill_chunk=chunk, page_size=8,
            quant_mode="native",
        ))
        outs[chunk] = eng.generate([list(prompt)], max_new=4)[0]
    assert outs[4] == outs[1] and outs[16] == outs[1], outs


# ---- recurrent lifecycle regressions ------------------------------------


def test_staggered_prefill_join_ssm():
    """A request admitted while another lane is mid-decode must not
    perturb either stream: per-row valid masking keeps a masked lane's
    recurrent state bit-unchanged through the joiner's prefill chunks."""
    cfg, params = _model("xlstm-1.3b")
    pa = _prompt_from_seed(cfg, 21, 11)
    pb = _prompt_from_seed(cfg, 22, 7)

    def fresh():
        return ContinuousEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=MAX_LEN, prefill_chunk=4, page_size=8,
            quant_mode="native",
        ))

    solo_a = fresh().generate([list(pa)], max_new=8)[0]
    solo_b = fresh().generate([list(pb)], max_new=8)[0]

    eng = fresh()
    ra = eng.submit(list(pa), max_new=8)
    for _ in range(3):
        eng.step()  # lane A is mid-decode when B arrives
    rb = eng.submit(list(pb), max_new=8)
    for _ in range(30):
        eng.step()
        if all(r.done for r in eng.scheduler.requests.values()):
            break
    assert eng.outputs[ra] == solo_a
    assert eng.outputs[rb] == solo_b


def test_preemption_resumes_recurrent_state_ssm():
    """ssm lanes hold zero pages, so page pressure never preempts them
    organically — evict one white-box and require the bit-identical
    resume that re-prefilling prompt+emitted guarantees through the
    sequential-state invariant (admission resets the lane's state)."""
    cfg, params = _model("xlstm-1.3b")
    prompts = [_prompt_from_seed(cfg, 31, 9), _prompt_from_seed(cfg, 32, 6)]

    def fresh():
        return ContinuousEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=MAX_LEN, prefill_chunk=4, page_size=8,
            quant_mode="native",
        ))

    calm = fresh().generate([list(p) for p in prompts], max_new=8)

    eng = fresh()
    rids = [eng.submit(list(p), max_new=8) for p in prompts]
    for _ in range(2):
        eng.step()
    victim = eng._youngest_lane()
    assert victim is not None
    eng._preempt(victim)
    for _ in range(40):
        eng.step()
        if all(r.done for r in eng.scheduler.requests.values()):
            break
    got = {r: eng.outputs[r] for r in rids}
    assert got == calm


@pytest.mark.slow
def test_preemption_resumes_recurrent_state_hybrid():
    """Hybrid lanes DO hold attention pages: a tight pool preempts
    organically, and the resumed stream must replay exactly — both the
    paged attention state and the re-prefilled mamba state."""
    cfg, params = _model("jamba-v0.1-52b")
    prompts = [_prompt_from_seed(cfg, 41, 12), _prompt_from_seed(cfg, 42, 9)]

    def run(n_pages, **kw):
        eng = ContinuousEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=MAX_LEN, prefill_chunk=4, page_size=8,
            quant_mode="native", n_pages=n_pages, **kw,
        ))
        out = eng.generate([list(p) for p in prompts], max_new=8)
        return eng, out

    _, calm = run(16)
    tight_eng, got = run(4, watermark_pages=0)
    assert tight_eng.stats()["preempted"] >= 1, "pool was not tight enough"
    assert got == calm
    tight_eng.alloc.check()


# ---- shared-prefix guards name the blocking feature (satellite c) -------


@pytest.mark.parametrize("arch,needle", [
    ("xlstm-1.3b", "recurrent state"),
    ("jamba-v0.1-52b", "mamba recurrent state"),
    ("h2o-danube-3-4b", "sliding_window"),
])
def test_shared_prefix_guard_names_blocking_feature(arch, needle):
    """Families are no longer rejected at engine construction; the one
    remaining exclusion (prefix sharing) must say exactly WHY."""
    cfg, params = _model(arch)
    eng = ContinuousEngine(cfg, params, ServeConfig(
        n_slots=2, max_len=MAX_LEN, prefill_chunk=4, page_size=8,
    ))
    with pytest.raises(ValueError) as exc:
        eng.register_shared_prefix([2, 3, 4])
    msg = str(exc.value)
    assert cfg.name in msg and "blocking feature" in msg and needle in msg


def test_continuous_engine_accepts_every_registry_family():
    """The engine.py:676 rejection is gone: construction succeeds for
    every conformance family (decode correctness is pinned above)."""
    for arch in FAMILY_ARCHS:
        cfg, params = _model(arch)
        eng = ContinuousEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=MAX_LEN, prefill_chunk=4, page_size=8,
        ))
        assert eng.cfg.family == cfg.family
