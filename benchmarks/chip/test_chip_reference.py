"""The configurations' references, found by name, on the CPU at a reduced
size: the dense decoder's required FLOPs, the law of its seeded weights and
its float32 reference against the program, references of other models
brought by files of their own (with laws of their own for leaves the dense
decoder does not have), and the refusal of a configuration that names
none."""
import dataclasses
import json
import os
import sys
import textwrap
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

from chipbench import harness, inputs, reference, spec  # noqa: E402

DENSE = spec.reference({"reference": "dense_decoder"})

GQA = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
       "vocab_size": 1000, "tie_word_embeddings": True}
MHA = dict(GQA, num_attention_heads=4, num_key_value_heads=4,
           num_hidden_layers=3, vocab_size=500)


@pytest.mark.parametrize("c, seq, want", [
    # per layer 64*64 (q) + 2*64*32 (k, v) + 64*64 (o) + 3*64*128 (mlp)
    # = 36864; 2 layers + a 64 x 1000 head = 137728 matmul params; causal
    # attention 3 passes x 2 layers x 2 products x 2*64*(8+1)/2 FLOPs
    (GQA, 8, 6 * 137728 + 6912),
    # with 4 kv heads q, k, v and o are 64*64 each: 40960 a layer with
    # the mlp; 3 layers + a 64 x 500 head = 154880
    (MHA, 16, 6 * 154880 + 3 * 3 * 2 * 2 * 64 * 17 / 2),
])
def test_flops_per_token_match_a_hand_count(c, seq, want):
    assert DENSE.train_flops_per_token(c, seq) == pytest.approx(want)


@pytest.mark.parametrize("config, want", [
    ("qwen2-1.5b-l4", 2_674_298_880),
    ("minicpm-2b-1chip", 3_811_525_632),
    ("qwen2-1.5b-l28", 10_318_639_104),
])
def test_flops_per_token_of_each_configuration_file(config, want):
    """What ``mfu`` divides by, at the cells' seq 4096, through the module
    the configuration names."""
    [entry] = [c for c in spec.benchmark()["configs"] if c["name"] == config]
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        c = json.load(f)
    assert spec.reference(c).train_flops_per_token(c, 4096) == want


@pytest.mark.parametrize("c", [GQA, MHA])
def test_matmul_params_agree_with_the_programs_parameter_count(c):
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(name="t", family="dense", num_layers=c[
        "num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=True)
    # n_params counts the padded embedding once and two norm scales a
    # layer; the matmul parameters are the head over the real vocabulary
    norms = 2 * cfg.d_model * cfg.num_layers
    padding = (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model
    assert DENSE.matmul_params(c) == cfg.n_params() - norms - padding


def _program_config(arch):
    """The program's reduced config of ``arch`` at float32, and the same
    model as a configuration file."""
    from repro.configs import run_config

    m = dataclasses.replace(run_config(arch, smoke=True), dtype="float32")
    c = {"hidden_size": m.d_model, "intermediate_size": m.d_ff,
         "num_attention_heads": m.num_heads,
         "num_key_value_heads": m.num_kv_heads, "head_dim": m.d_head,
         "num_hidden_layers": m.num_layers, "vocab_size": m.vocab_size,
         "vocab_pad_multiple": 256, "attention_bias": m.qkv_bias,
         "rope_theta": m.rope_theta, "rms_norm_eps": m.norm_eps,
         "tie_word_embeddings": m.tie_embeddings}
    return m, c


# The program's loss at float32 on the CPU and the reference agree to
# float32 summation order: ~1e-6 of a loss of ~6.  Zeroing one layer's MLP
# output moves the loss by ~1e-2 at this size.
REF_TOL = 1e-4


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "minicpm-2b"])
def test_reference_loss_matches_the_programs_loss(arch):
    import jax

    from repro.models.api import get_model

    m, c = _program_config(arch)
    api = get_model(m)
    words = inputs.seed_words(2**35 + 3)
    shapes = DENSE.param_shapes(c)
    params = jax.jit(lambda: inputs.make_params(DENSE, words, 0, shapes))()
    batch = inputs.make_tokens(words, 0, 0, 2, 1024, c["vocab_size"], 3.0)
    ref = float(DENSE.loss(params, batch, c))
    prog = float(api.loss(params, batch)[0])
    assert abs(ref - prog) < REF_TOL, (ref, prog)

    broken = jax.tree.map(lambda x: x, params)
    broken["layers"]["mlp"]["wd"] = params["layers"]["mlp"]["wd"].at[0].set(0)
    prog_broken = float(api.loss(broken, batch)[0])
    assert abs(ref - prog_broken) > 10 * REF_TOL, (ref, prog_broken)


# -- the dense reference reads as it did before it became a module ---------

TRAIN = {"optimizer": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-08,
         "weight_decay": 0.1, "decay_min_ndim": 2, "max_grad_norm": 1.0,
         "schedule": "cosine", "peak_lr": 5e-05, "floor_frac": 0.1}
TOYS = {
    "gqa_tied_bias": dict(GQA, vocab_pad_multiple=256, attention_bias=True,
                          rope_theta=1e6, rms_norm_eps=1e-5),
    "mha_untied": dict(GQA, num_key_value_heads=4, num_hidden_layers=3,
                       vocab_size=500, vocab_pad_multiple=256,
                       attention_bias=False, rope_theta=1e4,
                       rms_norm_eps=1e-5, tie_word_embeddings=False),
}


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_dense_reference_readings_are_bit_identical_to_the_recorded_ones(
        toy):
    """Loss, first gradient's leaf norms and the change after three steps
    on seeded weights, two blocks of 512 positions, as recorded in
    ``testdata/dense_decoder_readings.json`` from the reference before its
    layout, loss and FLOP count moved into ``references/dense_decoder.py``:
    the move changed no reading of the cells."""
    import jax

    with open(os.path.join(HERE, "testdata",
                           "dense_decoder_readings.json")) as f:
        want = json.load(f)[toy]
    c = TOYS[toy]
    words = inputs.seed_words(2**35 + 5)
    batches = lambda k: inputs.host_batch(  # noqa: E731
        words, 0, k, 2, 1024, c["vocab_size"], 3.0)
    shapes = DENSE.param_shapes(c)
    params = jax.jit(lambda w: inputs.make_params(DENSE, w, 0, shapes))(
        words)
    got = reference.run(DENSE, c, TRAIN, 1000, words, 0, batches)
    assert float(DENSE.loss(params, batches(0), c)) == want["loss"]
    assert got["grad_norms"] == want["grad_norms"]
    assert got["delta_norms"] == want["delta_norms"]


@pytest.mark.parametrize("config", [c["name"] for c in
                                    spec.benchmark()["configs"]])
def test_seeded_weights_are_bit_identical_to_the_recorded_ones(config):
    """Every leaf that ``make_params`` draws through the configuration's
    ``init``, at the file's published widths and two layers, hashes as
    recorded in ``testdata/seeded_weights_sha256.json`` from the harness
    before the law of each leaf moved into the reference: the move changed
    no cell's weights, so no reading or limit."""
    import hashlib

    import jax
    import numpy as np

    with open(os.path.join(HERE, "testdata",
                           "seeded_weights_sha256.json")) as f:
        rec = json.load(f)
    [entry] = [c for c in spec.benchmark()["configs"] if c["name"] == config]
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        c = dict(json.load(f), num_hidden_layers=rec["num_hidden_layers"])
    model = spec.reference(c)
    shapes = model.param_shapes(c)
    params = jax.jit(lambda w: inputs.make_params(model, w, rec["role"],
                                                  shapes))(
        inputs.seed_words(rec["seed"]))
    got = {p: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
           for p, x in zip(inputs.leaf_paths(shapes), jax.tree.leaves(params))}
    assert got == rec["sha256"][config]


# -- a configuration brings its own reference ------------------------------

TOY_REFERENCE = '''
"""A tied bigram model: logits = rms_norm(embed[token]) @ embed.T."""
import jax
import jax.numpy as jnp

from chipbench.inputs import normal
from chipbench.reference import mm


def param_shapes(c):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return {"embed": f32(c["vocab_size"], c["hidden_size"]),
            "final_norm": f32(c["hidden_size"])}


def init(path, key, shape):
    return normal(key, shape, 0.02)


def loss(params, batch, c, prec="f32"):
    h = params["embed"][batch["tokens"]]
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5) * (
        1.0 + params["final_norm"])
    logits = mm("bsd,vd->bsv", h, params["embed"], prec)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold[..., 0])


def train_flops_per_token(c, seq):
    return 6 * c["vocab_size"] * c["hidden_size"]
'''
TOY_MIXER = '''
"""Leaves the dense decoder does not have: one key, ``wo``, under two paths
with laws of their own, a per-channel decay ``A_log = log(U[1, 16])`` and a
step ``dt_bias = softplus^-1(log-uniform [1e-3, 1e-1])``, Mamba-2's
published laws, feeding a decaying running sum over the positions."""
import jax
import jax.numpy as jnp

from chipbench import inputs
from chipbench.reference import mm

B_WO_STD = 0.5


def param_shapes(c):
    V, D = c["vocab_size"], c["hidden_size"]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return {"embed": f32(V, D), "a": {"wo": f32(D, D)},
            "b": {"wo": f32(D, D)}, "A_log": f32(D), "dt_bias": f32(D)}


def init(path, key, shape):
    if path == "embed":
        return inputs.normal(key, shape, 0.02)
    if path == "a/wo":
        return inputs.normal(key, shape, inputs.fan_in_std(shape, (0,)))
    if path == "b/wo":
        return inputs.normal(key, shape, B_WO_STD)
    if path == "A_log":
        return jnp.log(inputs.uniform(key, shape, 1.0, 16.0))
    if path == "dt_bias":
        return inputs.inverse_softplus(
            inputs.log_uniform(key, shape, 1e-3, 1e-1))
    raise KeyError(path)


def loss(params, batch, c, prec="f32"):
    h = params["embed"][batch["tokens"]]
    decay = jnp.exp(-jax.nn.softplus(params["dt_bias"])
                    * jnp.exp(params["A_log"]))

    def carry(s, x):
        s = decay * s + x
        return s, s

    _, run = jax.lax.scan(carry, jnp.zeros_like(h[:, 0]),
                          jnp.moveaxis(h, 1, 0))
    h = h + jnp.moveaxis(run, 0, 1)
    for sub in ("a", "b"):
        h = h + jnp.tanh(mm("bsd,de->bse", h, params[sub]["wo"], prec))
    logits = mm("bsd,vd->bsv", h, params["embed"], prec)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold[..., 0])


def train_flops_per_token(c, seq):
    D = c["hidden_size"]
    return 6 * (c["vocab_size"] * D + 2 * D * D)
'''
MIXER = {"reference": "toy_mixer", "vocab_size": 96, "hidden_size": 32,
         "num_hidden_layers": 1, "train": TRAIN}
BIGRAM = {"reference": "toy_bigram", "vocab_size": 96, "hidden_size": 32,
          "num_hidden_layers": 1, "train": TRAIN}


@pytest.fixture
def references(tmp_path, monkeypatch):
    """A references directory holding only the two toy references."""
    (tmp_path / "toy_bigram.py").write_text(textwrap.dedent(TOY_REFERENCE))
    (tmp_path / "toy_mixer.py").write_text(textwrap.dedent(TOY_MIXER))
    monkeypatch.setattr(spec, "REFERENCES", tmp_path)
    return tmp_path


def _toy_plan(config=BIGRAM):
    import jax

    traffic = {"seq": 16, "batch": 2, "token_power": 3.0, "tenants": [],
               "warmup_steps": 6,
               "schedule_steps": 1000}
    return harness.Plan(config=config, traffic=traffic, seed=2**34 + 1,
                        seconds=0, trace=False, devices=jax.devices()[:1])


def test_a_second_reference_drives_the_layout_flops_and_reference_run(
        references):
    import jax

    plan = _toy_plan()
    assert plan.model.__file__ == str(references / "toy_bigram.py")
    assert plan.model.train_flops_per_token(BIGRAM, 16) == 6 * 96 * 32

    # the program's layout is checked against the toy's, not the dense one
    run = harness.Run(plan, 0.0)
    shapes = plan.role_shapes(0)
    opt = types.SimpleNamespace(init=lambda p: {})
    same = types.SimpleNamespace(abstract=lambda: shapes)
    state = run.init_state(0, same, opt, None)
    want = jax.jit(lambda w: inputs.make_params(plan.model, w, 0, shapes))(
        plan.words)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     state["params"], want))
    dense = DENSE.param_shapes(dict(GQA, attention_bias=False))
    other = types.SimpleNamespace(abstract=lambda: dense)
    with pytest.raises(ValueError, match="not the layout"):
        run.init_state(0, other, opt, None)

    got = harness.reference_readings(plan, 0)
    assert sorted(got["grad_norms"]) == sorted(got["delta_norms"]) == [
        "embed", "final_norm"]
    assert all(v > 0 for v in got["delta_norms"].values()), got


def test_a_reference_draws_its_own_leaves_by_path_and_law(references):
    """What the harness refused while it kept the dense decoder's laws:
    leaf keys outside them, one key under two paths with different laws,
    and a log-uniform law, through ``Run.init_state`` and the reference's
    three steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    plan = _toy_plan(MIXER)
    run = harness.Run(plan, 0.0)
    shapes = plan.role_shapes(0)
    opt = types.SimpleNamespace(init=lambda p: {})
    state = run.init_state(0, types.SimpleNamespace(abstract=lambda: shapes),
                           opt, None)
    p = jax.tree.map(np.asarray, state["params"])
    D = MIXER["hidden_size"]
    # A = exp(A_log) in [1, 16); softplus(dt_bias) in [1e-3, 1e-1)
    a = np.exp(p["A_log"])
    assert a.min() >= 1.0 and a.max() < 16.0 and np.ptp(a) > 7.5, a
    dt = np.asarray(jax.nn.softplus(jnp.asarray(p["dt_bias"])))
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() < 1e-1 * (1 + 1e-5), dt
    # uniform in the logarithm: as many steps under 1e-2 as over it
    assert 0.3 < np.mean(dt < 1e-2) < 0.7, dt
    # the two wo's, 1024 draws each, have their own std (to ~4%)
    assert abs(p["a"]["wo"].std() * np.sqrt(D) - 1.0) < 0.1, p["a"]["wo"].std()
    assert abs(p["b"]["wo"].std() / plan.model.B_WO_STD - 1.0) < 0.1

    got = harness.reference_readings(plan, 0)
    assert sorted(got["grad_norms"]) == sorted(got["delta_norms"]) == [
        "A_log", "a/wo", "b/wo", "dt_bias", "embed"]
    assert all(v > 0 for v in got["delta_norms"].values()), got


def test_weights_of_another_shape_or_type_than_the_layout_are_refused(
        references):
    import jax
    import jax.numpy as jnp

    plan = _toy_plan()
    shapes = plan.role_shapes(0)
    for bad in (lambda path, key, shape: jnp.zeros(shape[:-1]),
                lambda path, key, shape: jnp.zeros(shape, jnp.bfloat16)):
        model = types.SimpleNamespace(init=bad)
        with pytest.raises(ValueError, match="not float32"):
            jax.eval_shape(lambda w: inputs.make_params(model, w, 0, shapes),
                           plan.words)


@pytest.mark.parametrize("config, error", [
    ({}, "names no reference"),
    ({"reference": "dense_decoder_v0"}, "no reference 'dense_decoder_v0'"),
    ({"reference": "../references/dense_decoder"}, "names no reference"),
])
def test_a_configuration_without_a_usable_reference_is_refused(
        tmp_path, config, error):
    bench = spec.benchmark()
    cell = bench["workloads"][0]
    [entry] = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        c = json.load(f)
    c.pop("reference")
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(c, **config)))
    bench["configs"] = [dict(entry, file=str(path))]
    with pytest.raises(ValueError, match=error):
        spec.cell(cell["name"], bench)


def test_a_reference_without_its_three_functions_is_refused(references):
    (references / "half.py").write_text("def loss(p, b, c, prec='f32'):\n"
                                        "    return 0.0\n")
    with pytest.raises(ValueError, match="lacks.*param_shapes"):
        spec.reference({"reference": "half"})


def test_a_reference_without_init_is_refused(references):
    """A layout, a loss and a FLOP count, but no law for its weights."""
    body = (references / "toy_bigram.py").read_text()
    (references / "lawless.py").write_text(body.replace("def init(",
                                                        "def _init("))
    with pytest.raises(ValueError, match=r"lacks \['init'\]"):
        spec.reference({"reference": "lawless"})
