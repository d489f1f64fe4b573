"""The configurations' references, found by name, on the CPU at a reduced
size: the dense decoder's required FLOPs and its float32 reference against
the program, a reference of another model brought by a file of its own,
and the refusal of a configuration that names none."""
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
    params = jax.jit(lambda: inputs.make_params(words, 0, shapes))()
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
    params = jax.jit(lambda w: inputs.make_params(w, 0, shapes))(words)
    got = reference.run(DENSE, c, TRAIN, 1000, words, 0, batches)
    assert float(DENSE.loss(params, batches(0), c)) == want["loss"]
    assert got["grad_norms"] == want["grad_norms"]
    assert got["delta_norms"] == want["delta_norms"]


# -- a configuration brings its own reference ------------------------------

TOY_REFERENCE = '''
"""A tied bigram model: logits = rms_norm(embed[token]) @ embed.T."""
import jax
import jax.numpy as jnp

from chipbench.reference import mm


def param_shapes(c):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return {"embed": f32(c["vocab_size"], c["hidden_size"]),
            "final_norm": f32(c["hidden_size"])}


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
BIGRAM = {"reference": "toy_bigram", "vocab_size": 96, "hidden_size": 32,
          "num_hidden_layers": 1, "train": TRAIN}


@pytest.fixture
def references(tmp_path, monkeypatch):
    """A references directory holding only the toy bigram reference."""
    (tmp_path / "toy_bigram.py").write_text(textwrap.dedent(TOY_REFERENCE))
    monkeypatch.setattr(spec, "REFERENCES", tmp_path)
    return tmp_path


def _toy_plan():
    import jax

    traffic = {"seq": 16, "batch": 2, "token_power": 3.0, "tenants": [],
               "warmup_steps": 6,
               "schedule_steps": 1000}
    return harness.Plan(config=BIGRAM, traffic=traffic, seed=2**34 + 1,
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
    want = jax.jit(lambda w: inputs.make_params(w, 0, shapes))(plan.words)
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
