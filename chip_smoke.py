"""Bring-up check: train qwen2-1.5b at its published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one 2x2 v5e host, four chips

One chip: ``repro.launch.train.run`` trains qwen2-1.5b cut to ``LAYERS``
layers (depth is the only cut) at seq 4096, global batch 1, for 5 steps,
with one qwen2-1.5b background tenant on the same device.  Then the loss of
one seq-512 batch is computed on the chip and on the host CPU backend of the
same process, on the same params, and the two are compared.

Four chips (``--four-chips``) runs only what exists across chips: the
full-depth model under FSDP on a (data=4, model=1) mesh against a
(data=2, model=2) carving, and the depth-cut model on chips 0-1 with a
background tenant on the disjoint chips 2-3, against the same run without
the tenant.

Earlier lines print what was observed; times are bring-up observations, not
benchmark numbers.  The last line is one JSON object, printed only when
every check passed.  Without a TPU, or outside a checkout of the repo, the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-1.5b"
SEQ = 4096
# Deepest cut whose compiled fg step plus the same-device tenant's step
# (seq 32, 2 samples, BG_LAYERS deep) leave >= 2 GB of the chip's memory by
# memory_analysis() on a described v5e, counting both programs' temporaries
# as live at once: 5 layers take 10.33 + 4.40 GB, 6 would take 11.38 + 4.40.
# tests/test_tpu_compile.py holds this.  HBM_BYTES is the bytes_limit that
# memory_stats() reports on one v5e chip (15.75 GiB of its 16 GiB).
LAYERS = 5
BG_LAYERS = 1
STEPS = 5
# Four chips: all layers, global batch 4, FOUR_CHIP_STEPS steps on each
# carving; tests/test_tpu_compile.py holds each chip's share to HBM_BYTES.
FOUR_CHIP_BATCH = 4
FOUR_CHIP_STEPS = 3
# Peak learning rate of the smoke runs.  At the schedule's default 3e-4 a
# 5-step run has no warmup, and Adam's first, sign-like updates overshoot:
# the 4-layer loss went 12.098 -> 15.489 at step 2 on a v5e, and a rise
# that steep amplifies bf16 rounding differences between two carvings into
# loss differences of 0.7.  At 5e-5 the loss falls at every step.
LR = 5e-5
HBM_BYTES = 16_909_336_064
HEADROOM_BYTES = 2e9

# Chip-vs-host loss agreement on one seq-512 batch.  bf16 keeps 8
# significant bits (unit roundoff 2**-8 ~ 0.004) and the logits are O(1),
# so each per-token loss carries rounding error of that order: on the host,
# bf16 against f32 compute of this model differs by 0.007 rms per token and
# 0.0005 on the mean.  Two bf16 implementations may differ by about twice
# that.  An fp8 (e4m3) weight path moves the per-token loss by 0.14 rms and
# the mean by 0.006; zeroing one layer's MLP output moves them by 0.47 and
# 0.008 (host measurements at 3 layers).  The tolerances sit between.
CHECK_SEQ = 512
TOK_RMS_TOL = 0.03
MEAN_TOL = 0.003
# Two carvings of the same training run.  At step 0 both hold the same
# params and batch, and only the carving differs: the losses are one
# forward pass summed in another order, so they agree to the bf16 shift of
# a mean loss (host: 0.0005), held at 1e-3 -- a dropped term (0.008) or an
# fp8 path (0.006) in any carving's forward fails it.  Later steps follow
# updates that Adam's first, sign-like steps amplify: a rounding-level
# gradient difference near zero flips a whole +-lr update, so the gap grows
# with each step.  It must stay under CARVING_STEP_FRAC of what training
# moved the loss since step 0; an update that one carving applies to only
# part of the params, or not at all, moves the loss by a large part of that.
CARVING_STEP0_TOL = 1e-3
CARVING_STEP_FRAC = 0.1


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def train_args(*extra):
    from repro.launch.train import build_parser

    return build_parser().parse_args(
        ["--arch", ARCH, "--lr", str(LR), *map(str, extra)])


def run_clean(args):
    """``launch.train.run`` that fails on any absorbed fault."""
    from repro.launch.train import run

    report = run(args)
    if report.restarts or report.mitigations.count("failure"):
        fail(f"training absorbed a fault: restarts={report.restarts}, "
             f"events={report.mitigations.events}")
    if report.steps_done != args.steps:
        fail(f"{report.steps_done} of {args.steps} steps done")
    if not all(math.isfinite(x) for x in report.losses):
        fail(f"non-finite loss: {report.losses}")
    return report


def show(label, report):
    print(f"{label}: compile {report.compile_s:.2f} s (first step, apart "
          f"from the step times)")
    print(f"{label}: step wall times, s (bring-up observation, not a "
          f"benchmark number): "
          + " ".join(f"{t:.4f}" for t in report.step_times))
    print(f"{label}: losses " + " ".join(f"{x:.6f}" for x in report.losses))
    print(f"{label}: fg devices {report.fg_devices}, bg_steps "
          f"{report.bg_steps}, tenants {report.bg_tenants}")


def token_losses(cfg, params, tokens, labels):
    """Per-position next-token loss of ``cfg`` on whichever device holds
    ``params``."""
    import jax
    import jax.numpy as jnp

    from repro.models.api import get_model

    api = get_model(cfg)

    @jax.jit
    def nll(p, t, y):
        logits = api.forward(p, t).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    return nll(params, tokens, labels)


def chip_vs_host(cfg, seed: int = 0):
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.models.api import get_model

    chip, host = jax.devices()[0], jax.devices("cpu")[0]
    init = jax.jit(get_model(cfg).init, out_shardings=SingleDeviceSharding(chip))
    params = init(jax.random.PRNGKey(seed))
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, CHECK_SEQ + 1), dtype=np.int32)
    tokens, labels = toks[:, :-1], toks[:, 1:]
    on_chip = np.asarray(token_losses(
        cfg, params, jax.device_put(tokens, chip), jax.device_put(labels, chip)))
    on_host = np.asarray(token_losses(
        cfg, jax.device_put(params, host), jax.device_put(tokens, host),
        jax.device_put(labels, host)))
    d = on_chip.astype(np.float64) - on_host
    mean_d, rms_d = abs(d.mean()), float(np.sqrt((d ** 2).mean()))
    print(f"chip vs host CPU, seq {CHECK_SEQ}: loss {on_chip.mean():.6f} vs "
          f"{on_host.mean():.6f}; |mean diff| {mean_d:.6f} (tol {MEAN_TOL}), "
          f"per-token rms diff {rms_d:.6f} (tol {TOK_RMS_TOL}), max "
          f"{np.abs(d).max():.6f}")
    if not (mean_d <= MEAN_TOL and rms_d <= TOK_RMS_TOL):
        fail("chip and host losses disagree beyond the bf16 tolerance")


def one_chip():
    import jax

    from repro.configs import run_config
    from repro.models.api import get_model
    from repro.models.layers import param_count

    cfg = run_config(ARCH, layers=LAYERS)
    print(f"{ARCH}: published widths (d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}); depth cut to N={LAYERS} of "
          f"{run_config(ARCH).num_layers} layers; "
          f"{param_count(get_model(cfg).schema)} params")
    report = run_clean(train_args(
        "--layers", LAYERS, "--bg-layers", BG_LAYERS, "--seq", SEQ,
        "--batch", 1, "--steps", STEPS, "--bg-arch", ARCH))
    show("train", report)
    [(placement, ids)] = report.bg_tenants
    print(f"bg tenant: {placement}, {BG_LAYERS} layer(s), devices {ids}")
    stats = jax.devices()[0].memory_stats() or {}
    for k in ("peak_bytes_in_use", "bytes_limit"):
        print(f"{k}: {stats.get(k, 'not reported')}")

    losses = report.losses
    ln_v = math.log(cfg.vocab_size)
    if abs(losses[0] - ln_v) > 0.5:
        fail(f"step-0 loss {losses[0]:.4f} not within 0.5 of ln(vocab) "
             f"{ln_v:.4f}")
    if not all(b < a for a, b in zip(losses, losses[1:])):
        fail(f"loss did not decrease at every one of {STEPS} steps: {losses}")
    if placement != "same-device" or report.bg_steps <= 0:
        fail(f"bg tenant did not run same-device: {report.bg_tenants}, "
             f"bg_steps={report.bg_steps}")
    chip_vs_host(cfg)


def four_chips():
    # (a) full depth, FSDP over four chips, against a (2, 2) carving
    full = ("--seq", SEQ, "--batch", FOUR_CHIP_BATCH, "--steps",
            FOUR_CHIP_STEPS)
    fsdp = run_clean(train_args("--data", 4, "--model", 1, *full))
    show("full depth (data=4, model=1)", fsdp)
    tp = run_clean(train_args("--data", 2, "--model", 2, *full))
    show("full depth (data=2, model=2)", tp)
    for k, (a, b) in enumerate(zip(fsdp.losses, tp.losses)):
        moved = fsdp.losses[0] - a
        tol = CARVING_STEP0_TOL if k == 0 else CARVING_STEP_FRAC * moved
        print(f"step {k}: carving gap {abs(a - b):.6g} (tol {tol:.6g})")
        if not abs(a - b) <= tol:
            fail(f"carvings disagree at step {k}: {fsdp.losses} vs "
                 f"{tp.losses}")
    print("carvings agree")

    # (b) depth-cut fg on chips 0-1, one tenant on the chips left over
    cut = ("--layers", LAYERS, "--bg-layers", BG_LAYERS, "--data", 2,
           "--seq", SEQ, "--batch", 2, "--steps", STEPS)
    alone = run_clean(train_args(*cut))
    show("fg alone", alone)
    shared = run_clean(train_args(*cut, "--bg-arch", ARCH))
    show("fg with tenant", shared)
    [(placement, bg_ids)] = shared.bg_tenants
    print(f"fg mesh devices {shared.fg_devices}, bg mesh devices {bg_ids} "
          f"({placement})")
    if placement != "disjoint" or set(bg_ids) & set(shared.fg_devices):
        fail("bg tenant is not on devices disjoint from the fg mesh")
    if shared.bg_steps <= 0:
        fail("bg tenant took no steps")
    if shared.losses != alone.losses:
        fail(f"fg losses changed with the tenant: {alone.losses} vs "
             f"{shared.losses}")
    print("fg losses bit-identical with and without the tenant")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (one 2x2 v5e host)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no repro package under {ROOT}/src: run from a checkout")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {devs[0].platform} devices only")
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        fail(f"{want} chips wanted, JAX sees {len(devs)}")
    print(f"device: {devs[0].device_kind}, count {len(devs)}")

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
