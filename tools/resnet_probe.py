"""ResNet ceiling investigation (VERDICT r4 item 2): per-layer conv
timing + HLO dump + targeted experiments, on the real chip —
ResNet-18 (``layers``/``bn``/``block``/``hlo``) and ResNet-50 (``r50``).

The bench headline has sat at ~46-48% MFU for three rounds on the claim
that "CIFAR-scale early convs under-fill the MXU". This tool replaces
the claim with numbers:

- ``layers``: fori-timed fwd and fwd+bwd of every distinct conv shape in
  the ResNet-18 CIFAR step at the bench batch (1024, bf16), with
  achieved TFLOP/s and % of chip peak per layer — the weighted sum IS
  the model-level ceiling if the per-layer numbers are efficient.
- ``bn``: the BatchNorm+ReLU junction at each stage's shape (f32 stats
  on bf16 streams, the model's convention) — is the normalization
  breaking conv fusion expensively?
- ``block``: full BasicBlock fwd+bwd per stage (conv+BN+ReLU+residual),
  so (block − 2×conv − 2×bn) exposes unfused overhead.
- ``hlo``: dump the optimized HLO of the bench train step and print a
  fusion census (convolution count, fusion count, largest buffers).

``r50`` runs the same per-conv harness over every distinct ResNet-50
CIFAR conv shape at the fori-bench batch 256 (fwd+bwd only).

Usage: ``python tools/resnet_probe.py layers bn block r50`` (any subset).
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import _peak_flops  # noqa: E402
from tools.micro_lm import time_fn  # noqa: E402

B = 1024  # the bench per-chip batch

# Distinct conv shapes in the CIFAR ResNet-18 step: (name, C_in, C_out,
# H_in, W_in, k, stride, count) — count = how many times the shape runs
# per forward (projection 1x1s listed separately).
CONVS = [
    ("stem 3->64 @32", 3, 64, 32, 32, 3, 1, 1),
    ("s1 64->64 @32", 64, 64, 32, 32, 3, 1, 4),
    ("s2 64->128 @32/s2", 64, 128, 32, 32, 3, 2, 1),
    ("s2 128->128 @16", 128, 128, 16, 16, 3, 1, 3),
    ("s2 proj 64->128 @32/s2", 64, 128, 32, 32, 1, 2, 1),
    ("s3 128->256 @16/s2", 128, 256, 16, 16, 3, 2, 1),
    ("s3 256->256 @8", 256, 256, 8, 8, 3, 1, 3),
    ("s3 proj 128->256 @16/s2", 128, 256, 16, 16, 1, 2, 1),
    ("s4 256->512 @8/s2", 256, 512, 8, 8, 3, 2, 1),
    ("s4 512->512 @4", 512, 512, 4, 4, 3, 1, 3),
    ("s4 proj 256->512 @8/s2", 256, 512, 8, 8, 1, 2, 1),
]


def conv_flops(ci, co, h, w, k, stride, batch):
    """Forward matmul FLOPs (2/MAC) of a SAME conv."""
    ho, wo = (h + stride - 1) // stride, (w + stride - 1) // stride
    return 2.0 * batch * ho * wo * k * k * ci * co


def run_layers(peak, batch=None, convs=None, fwd_too=True):
    batch = batch or B
    convs = convs if convs is not None else CONVS
    print(f"== per-conv timing, batch {batch}, bf16, peak {peak/1e12:.0f} TF/s")
    key = jax.random.PRNGKey(0)
    total_fwd_t = total_fb_t = total_fwd_f = 0.0
    for name, ci, co, h, w, k, stride, count in convs:
        x = jax.random.normal(key, (batch, h, w, ci), jnp.bfloat16)
        wgt = jax.random.normal(key, (k, k, ci, co), jnp.bfloat16) * 0.05
        dn = jax.lax.conv_dimension_numbers(
            x.shape, wgt.shape, ("NHWC", "HWIO", "NHWC")
        )

        def conv(x, wgt):
            # Pure-bf16 conv matching the model's Conv2D (nn/layers.py:125 —
            # no preferred_element_type; the MXU accumulates f32 internally).
            return jax.lax.conv_general_dilated(
                x, wgt, (stride, stride), "SAME", dimension_numbers=dn,
            )

        def fb(x, wgt):
            # fwd+bwd via vjp against a fixed-scale cotangent sum.
            y, pull = jax.vjp(conv, x, wgt)
            return pull(y)  # dX and dW with dY = y (shape-right cotangent)

        f = conv_flops(ci, co, h, w, k, stride, batch)
        # Sub-ms kernels: long fori windows so host-timing jitter differences out.
        line = f"   {name:26s} x{count}:"
        if fwd_too:
            t_fwd = time_fn(f"{name} fwd", conv, x, wgt, iters_lo=24, iters_hi=96)
            line += f" fwd {f/1e9:6.1f} GF {f/t_fwd/peak*100:5.1f}% |"
            total_fwd_t += count * t_fwd
        t_fb = time_fn(f"{name} fwd+bwd", fb, x, wgt, iters_lo=24, iters_hi=96)
        # fwd+bwd = 3x fwd FLOPs (dX + dW each equal the fwd contraction)
        print(line + f" fwd+bwd {3*f/t_fb/peak*100:5.1f}% of peak")
        total_fb_t += count * t_fb
        total_fwd_f += count * f
    if fwd_too:
        print(
            f"   SUM convs: fwd {total_fwd_t*1e3:.2f} ms"
            f" ({total_fwd_f/total_fwd_t/peak*100:.1f}% of peak),"
            f" fwd+bwd {total_fb_t*1e3:.2f} ms"
            f" ({3*total_fwd_f/total_fb_t/peak*100:.1f}% of peak)"
        )
    else:
        print(
            f"   SUM convs fwd+bwd {total_fb_t*1e3:.2f} ms"
            f" ({3*total_fwd_f/total_fb_t/peak*100:.1f}% of peak)"
        )


def run_bn(peak):
    print("== BatchNorm+ReLU at stage shapes (f32 stats, bf16 stream)")
    from tpudml.nn.layers import BatchNorm

    key = jax.random.PRNGKey(1)
    for ch, h in [(64, 32), (128, 16), (256, 8), (512, 4)]:
        x = jax.random.normal(key, (B, h, h, ch), jnp.bfloat16)
        bn = BatchNorm(ch)
        params, state = bn.init(jax.random.PRNGKey(2))

        def bnrelu(x):
            # Model convention (BasicBlock._bn): BN consumes the bf16 stream
            # directly; stats accumulate in f32 inside BatchNorm.apply.
            y, st = bn.apply(params, state, x, train=True)
            return jax.nn.relu(y).astype(jnp.bfloat16), st["mean"]

        time_fn(f"bn+relu {ch}ch @{h}x{h}", bnrelu, x)


def run_block(peak):
    print("== full BasicBlock fwd+bwd per stage")
    from tpudml.models.resnet import BasicBlock

    key = jax.random.PRNGKey(3)
    for ci, co, h, stride in [
        (64, 64, 32, 1), (64, 128, 32, 2), (128, 256, 16, 2),
        (256, 512, 8, 2),
    ]:
        blk = BasicBlock(ci, co, stride, compute_dtype=jnp.bfloat16)
        params, state = blk.init(jax.random.PRNGKey(4))
        x = jax.random.normal(key, (B, h, h, ci), jnp.bfloat16)

        def fb(x):
            def f(x):
                y, _ = blk.apply(params, state, x, train=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.value_and_grad(f)(x)

        time_fn(f"block {ci}->{co} @{h} s{stride} fwd+bwd", fb, x)


def run_hlo():
    from bench import _make_step_body
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_classification
    from tpudml.models import ResNet18
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState

    model = ResNet18(compute_dtype=jnp.bfloat16)
    opt = make_optimizer("sgd", 0.1, momentum=0.9)
    images, labels = synthetic_classification(B, (32, 32, 3), 10, seed=0)
    body = _make_step_body(model, opt)
    ts0 = TrainState.create(model, opt, seed_key(0))
    txt = (
        jax.jit(body)
        .lower(ts0, jnp.asarray(images), jnp.asarray(labels))
        .compile()
        .as_text()
    )
    out = "/tmp/resnet_hlo.txt"
    with open(out, "w") as f:
        f.write(txt)
    convs = txt.count(" convolution(")
    fusions = txt.count(" fusion(")
    customs = txt.count(" custom-call(")
    print(f"wrote {len(txt)} chars to {out}")
    print(f"census: {convs} convolutions, {fusions} fusions, {customs} custom-calls")


# ResNet-50 CIFAR: EVERY distinct conv shape at the fori-bench batch 256
# (name, C_in, C_out, H, W, k, stride, count/fwd), from models/resnet.py
# ResNet(stage_sizes=(3,4,6,3), block="bottleneck"): block 0 of each
# stage reduces from the previous stage's width (and carries the stride
# and the 1x1 projection); blocks 1+ reduce from 4*mid.
R50_B = 256
R50_CONVS = [
    ("stem 3->64 @32", 3, 64, 32, 32, 3, 1, 1),
    ("s1 1x1 64->64 @32", 64, 64, 32, 32, 1, 1, 1),
    ("s1 1x1 256->64 @32", 256, 64, 32, 32, 1, 1, 2),
    ("s1 3x3 64->64 @32", 64, 64, 32, 32, 3, 1, 3),
    ("s1 1x1 64->256 @32 (+proj)", 64, 256, 32, 32, 1, 1, 4),
    ("s2 1x1 256->128 @32", 256, 128, 32, 32, 1, 1, 1),
    ("s2 1x1 512->128 @16", 512, 128, 16, 16, 1, 1, 3),
    ("s2 3x3 128->128 @32/s2", 128, 128, 32, 32, 3, 2, 1),
    ("s2 3x3 128->128 @16", 128, 128, 16, 16, 3, 1, 3),
    ("s2 1x1 128->512 @16", 128, 512, 16, 16, 1, 1, 4),
    ("s2 proj 256->512 @32/s2", 256, 512, 32, 32, 1, 2, 1),
    ("s3 1x1 512->256 @16", 512, 256, 16, 16, 1, 1, 1),
    ("s3 1x1 1024->256 @8", 1024, 256, 8, 8, 1, 1, 5),
    ("s3 3x3 256->256 @16/s2", 256, 256, 16, 16, 3, 2, 1),
    ("s3 3x3 256->256 @8", 256, 256, 8, 8, 3, 1, 5),
    ("s3 1x1 256->1024 @8", 256, 1024, 8, 8, 1, 1, 6),
    ("s3 proj 512->1024 @16/s2", 512, 1024, 16, 16, 1, 2, 1),
    ("s4 1x1 1024->512 @8", 1024, 512, 8, 8, 1, 1, 1),
    ("s4 1x1 2048->512 @4", 2048, 512, 4, 4, 1, 1, 2),
    ("s4 3x3 512->512 @8/s2", 512, 512, 8, 8, 3, 2, 1),
    ("s4 3x3 512->512 @4", 512, 512, 4, 4, 3, 1, 2),
    ("s4 1x1 512->2048 @4", 512, 2048, 4, 4, 1, 1, 3),
    ("s4 proj 1024->2048 @8/s2", 1024, 2048, 8, 8, 1, 2, 1),
]


def run_r50(peak):
    print("== ResNet-50 per-conv timing (shared harness, fwd+bwd only)")
    run_layers(peak, batch=R50_B, convs=R50_CONVS, fwd_too=False)


def main():
    which = set(sys.argv[1:]) or {"layers"}
    peak = _peak_flops(jax.devices()[0]) or 197e12
    if "hlo" in which:
        run_hlo()
    if "layers" in which:
        run_layers(peak)
    if "bn" in which:
        run_bn(peak)
    if "block" in which:
        run_block(peak)
    if "r50" in which:
        run_r50(peak)


if __name__ == "__main__":
    main()
