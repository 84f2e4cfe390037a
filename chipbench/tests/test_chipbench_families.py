"""A configuration's model is its family module, found by name.

The dense family gives, at the tiny size, exactly what the dense code
gave before it moved into ``families/dense.py`` (commit
45965934024f32e9a96cdd35624c207f40ef9bfa): the same served tree, the
same reference gaps bit for bit, the same work counts.  And a family
written as files only is found, builds, serves and is judged."""

import hashlib
import json
import time

import jax
import numpy as np
import pytest

from chipbench import reference, roofline
from chipbench.bench import HERE, load_cell, load_family
from chipbench.harness import run_cell
from chipbench.tests.tiny import config, make_bench, on_cpu
from chipbench.traffic import derive

dense = load_family(HERE, "dense")

SEED = 2**31 + 7
SEQS = [([3, 17, 42, 99, 7, 250, 31, 8, 64], [5, 5, 120, 77, 2, 200]),
        ([11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21, 22, 23, 24, 25, 26],
         [240, 9, 9, 33])]

# read at the commit above, on the CPU, with the code then in
# chipbench/weights.py, reference.py and roofline.py
BEFORE = {
    "slot": {
        "params": "5f4724f104cb22fc87aaf636453a1e93"
                  "ec9130533717ba8069da9d62a407b163",
        "gap": [1.6260930299758911, 2.7735555171966553, 1.8209744691848755,
                1.9530696868896484, 2.004383087158203, 3.0550694465637207,
                2.8483753204345703, 2.773423433303833, 0.7320607900619507,
                2.063610076904297],
        "margin": [0.14978742599487305, 0.27086305618286133,
                   0.19377398490905762, 0.18501067161560059,
                   0.6879339218139648, 0.06703424453735352,
                   0.1860036849975586, 0.24982285499572754,
                   0.2344224452972412, 0.18159818649291992],
        "control_gap": [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.1860036849975586,
                        0.24982285499572754, 0.3583972454071045, 0.0],
    },
    "continuous": {
        "params": "f9e090f8e80316796f7c59d64b53157f"
                  "86cda5c1e109c306da3f2180b1fb5d65",
        "gap": [1.425327181816101, 2.8707830905914307, 1.9333786964416504,
                1.8311784267425537, 1.803080677986145, 2.337157964706421,
                2.437711238861084, 2.5688018798828125, 0.4715414047241211,
                2.177835464477539],
        "margin": [0.2854313850402832, 0.3555114269256592,
                   0.29685258865356445, 0.24956464767456055,
                   0.919083833694458, 0.0008385181427001953,
                   0.19346237182617188, 0.051775455474853516,
                   0.4715414047241211, 0.5690698623657227],
        "control_gap": [0.0, 0.0, 0.0, 0.0, 0.0, 0.13850188255310059, 0.0,
                        0.0, 0.0, 0.0],
    },
    # position_flops at positions 0, 1023, 4095; prompt_flops of 1500
    # positions; forward_min_time_s at 16 rows with the LM head and at 256
    # rows without, on the v5e peaks
    "danube3-4b-int4": {
        "position_flops": [7432151040, 7809269760, 8941731840],
        "prompt_flops": 11562670080000, "lm_head_flops": 245760000,
        "forward_min_time_s": [0.0024294993894993897, 0.004841059273282443],
    },
    "starcoder2-7b-int4": {
        "position_flops": [6946062336, 7247757312, 8153726976],
        "prompt_flops": 10750648320000, "lm_head_flops": 452984832,
        "forward_min_time_s": [0.0023198163614163614, 0.004538858861824045],
    },
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("engine", ["slot", "continuous"])
def test_dense_family_gives_the_values_it_gave_before(engine):
    model = config(engine)["model"]
    want = BEFORE[engine]
    m = dense.Model.from_config(model)
    assert _digest(dense.params(m, jax.random.PRNGKey(SEED))) == \
        want["params"]
    got = reference.gaps(dense, model, SEED, SEQS, n_rows=2, seq_len=44,
                         control="int4")
    for key in ("gap", "margin", "control_gap"):
        assert got[key].tolist() == want[key], key


@pytest.mark.parametrize("name", ["danube3-4b-int4", "starcoder2-7b-int4"])
def test_dense_family_counts_the_work_it_counted_before(name):
    model = json.loads((HERE / "configs" / f"{name}.json").read_text())[
        "model"]
    want = BEFORE[name]
    peak = roofline.peaks("TPU v5 lite")
    assert [dense.position_flops(model, p) for p in (0, 1023, 4095)] == \
        want["position_flops"]
    assert dense.prompt_flops(model, 1500) == want["prompt_flops"]
    assert dense.lm_head_flops(model) == want["lm_head_flops"]
    assert [dense.forward_min_time_s(model, r, peak, head)
            for r, head in ((16, True), (256, False))] == \
        want["forward_min_time_s"]


# A model the benchmark had no module for: multi-head attention (as many
# KV heads as query heads), full causal, tanh-GELU MLP, no biases, its
# weights drawn with keys folded in by name.  Served through the
# program's starcoder2 path, whose tree it shares.
TOY = '''
import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import roofline
from chipbench.reference import HIGHEST, qlinear, quantize_weight, rmsnorm, \\
    rope
from chipbench.weights import centred, normal

NAMES = ("wq", "wk", "wv", "wo", "up", "down")


@dataclasses.dataclass(frozen=True)
class Model:
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, model):
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in model.items() if k in names})


def _draw(m, key, i):
    k = jax.random.fold_in(key, 1000 + i)
    d, f = m.d_model, m.d_ff
    out = (2 * m.n_layers) ** -0.5
    shapes = {"wq": (d, d, 1.0), "wk": (d, d, 1.0), "wv": (d, d, 1.0),
              "wo": (d, d, out), "up": (d, f, 1.0), "down": (f, d, out)}
    w = {n: normal(jax.random.fold_in(k, j), *shapes[n])
         for j, n in enumerate(NAMES)}
    w["down"] = centred(w["down"])
    return w


def embed_and_head(m, seed_key):
    return (jax.random.normal(jax.random.fold_in(seed_key, 1),
                              (m.vocab_size, m.d_model), jnp.bfloat16),
            normal(jax.random.fold_in(seed_key, 2), m.d_model, m.vocab_size))


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(m, seed_key, i):
    return _draw(m, seed_key, i)


@functools.partial(jax.jit, static_argnums=(0,))
def params(m, seed_key):
    layers = [_draw(m, seed_key, i) for i in range(m.n_layers)]
    stack = lambda n: {"w": jnp.stack([w[n] for w in layers])}
    ones = jnp.ones((m.n_layers, m.d_model), jnp.bfloat16)
    embed, head = embed_and_head(m, seed_key)
    return {"embed": {"w": embed},
            "groups": {"ln1": {"scale": ones},
                       "attn": {n: stack(n) for n in NAMES[:4]},
                       "ln2": {"scale": ones},
                       "mlp": {n: stack(n) for n in NAMES[4:]}},
            "final_norm": {"scale": jnp.ones((m.d_model,), jnp.bfloat16)},
            "lm_head": {"w": head}}


@functools.partial(jax.jit, static_argnums=(0, 3))
def layer(m, x, w, bits):
    w = {n: quantize_weight(a) for n, a in w.items()}
    lin = lambda a, n: qlinear(a, w[n], bits)
    hd = m.d_model // m.n_heads

    def one(xs):
        s = xs.shape[0]
        pos = jnp.arange(s)
        h = rmsnorm(xs, m.norm_eps)
        q, k, v = (lin(h, n).reshape(s, m.n_heads, hd)
                   for n in ("wq", "wk", "wv"))
        q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd**-0.5
        sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -1e30)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HIGHEST).reshape(s, m.d_model)
        xs = xs + lin(o, "wo")
        f = jax.nn.gelu(lin(rmsnorm(xs, m.norm_eps), "up"), approximate=True)
        return xs + lin(f, "down")

    return jax.lax.map(one, x)


def _linear_params(model):
    d, f = model["d_model"], model["d_ff"]
    return 4 * d * d + 2 * d * f


def position_flops(model, pos):
    return model["n_layers"] * (2 * _linear_params(model)
                                + 4 * model["d_model"] * (pos + 1))


def prompt_flops(model, length):
    return sum(position_flops(model, p) for p in range(length))


def lm_head_flops(model):
    return 2 * model["d_model"] * model["vocab_size"]


def forward_min_time_s(model, rows, peak, lm_head):
    d, f = model["d_model"], model["d_ff"]
    shapes = [(d, d)] * 4 + [(d, f), (f, d)]
    shapes = shapes * model["n_layers"]
    if lm_head:
        shapes.append((d, model["vocab_size"]))
    return sum(roofline.min_time_s(*roofline.int4_linear_cost(rows, k, n),
                                   peak["int8_ops"], peak["hbm_bytes_per_s"])
               for k, n in shapes)
'''


def test_a_family_added_as_files_only_serves_and_is_judged(tmp_path,
                                                           monkeypatch):
    on_cpu(monkeypatch)
    cell = make_bench(tmp_path, engine="continuous", mix_name="toymix")
    bench = tmp_path / "chipbench"
    (bench / "families" / "toy.py").write_text(TOY)
    path = bench / "configs" / "tiny-continuous.json"
    conf = json.loads(path.read_text())
    conf["family"] = "toy"
    conf["model"].update(n_kv_heads=conf["model"]["n_heads"], qkv_bias=False)
    path.write_text(json.dumps(conf))

    got = load_cell(cell, root=tmp_path, bench_dir=bench)
    assert got.family.__file__ == str(bench / "families" / "toy.py")
    res = run_cell(got, seed=2**31 + 41, seconds=2.0, trace=False,
                   t0=time.perf_counter(), controls=("int4",))
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_checked"]["value"] >= 8
    assert res["controls"]["int4"] is False

    # what was served and judged is the toy's tree, not the dense draw
    m = got.family.Model.from_config(conf["model"])
    key = jax.random.PRNGKey(derive(2**31 + 41, "weights"))
    toy = got.family.params(m, key)["groups"]["attn"]["wq"]["w"]
    drawn = dense.params(dense.Model.from_config(conf["model"]), key)
    assert toy.shape == drawn["groups"]["attn"]["wq"]["w"].shape
    assert not (toy == drawn["groups"]["attn"]["wq"]["w"]).all()
