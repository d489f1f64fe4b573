"""The required-FLOPs function and the float32 reference, on the CPU at a
reduced size."""
import dataclasses
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

from chipbench import flops, inputs, reference  # noqa: E402

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
    assert flops.train_flops_per_token(c, seq) == pytest.approx(want)


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
    assert flops.matmul_params(c) == cfg.n_params() - norms - padding


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
    shapes = reference.param_shapes(c)
    params = jax.jit(lambda: inputs.make_params(words, 0, shapes))()
    batch = inputs.make_tokens(words, 0, 0, 2, 1024, c["vocab_size"], 3.0)
    ref = float(reference.loss(params, batch, c))
    prog = float(api.loss(params, batch)[0])
    assert abs(ref - prog) < REF_TOL, (ref, prog)

    broken = jax.tree.map(lambda x: x, params)
    broken["layers"]["mlp"]["wd"] = params["layers"]["mlp"]["wd"].at[0].set(0)
    prog_broken = float(api.loss(broken, batch)[0])
    assert abs(ref - prog_broken) > 10 * REF_TOL, (ref, prog_broken)
