"""ResNet family (paper's own experiment models, Figs. 8-10).

Built as an explicit *layer list* so OpTorch's ``checkpoint_sequential``
applies exactly as in the paper: segments of the sequential stack are
rematted, only segment inputs are stored.  GroupNorm replaces BatchNorm
(stateless — no running stats to thread through pjit).

The first layer is the E-D *decode layer* when the input is a packed
uint32 batch (paper II.A.2: "a custom deep learning layer to decode").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.pack import ops as pack_ops
from repro.models.layers import dense_init


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    arch_id: str = "resnet18"
    stage_sizes: Sequence[int] = (2, 2, 2, 2)
    widths: Sequence[int] = (64, 128, 256, 512)
    bottleneck: bool = False
    num_classes: int = 10
    groups: int = 8
    stem_stride: int = 1          # 1 for CIFAR, 2 for 512x512 memory runs


def resnet18(num_classes=10, **kw) -> ResNetConfig:
    return ResNetConfig("resnet18", (2, 2, 2, 2), (64, 128, 256, 512),
                        False, num_classes, **kw)


def resnet50(num_classes=10, **kw) -> ResNetConfig:
    return ResNetConfig("resnet50", (3, 4, 6, 3), (64, 128, 256, 512),
                        True, num_classes, **kw)


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x.astype(w.dtype), w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, scale, bias, groups):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g).astype(jnp.float32)
    mean = xg.mean((1, 2, 4), keepdims=True)
    var = xg.var((1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return (xg.reshape(n, h, w, c) * scale + bias).astype(x.dtype)


def _conv_init(key, kh, kw, cin, cout):
    return dense_init(key, (kh, kw, cin, cout), in_axis=0) / (kh * kw) ** 0.5


def init_params(cfg: ResNetConfig, key) -> dict:
    keys = iter(jax.random.split(key, 256))
    p: dict = {"stem": {"w": _conv_init(next(keys), 3, 3, 3, cfg.widths[0]),
                        "s": jnp.ones((cfg.widths[0],)),
                        "b": jnp.zeros((cfg.widths[0],))}}
    cin = cfg.widths[0]
    blocks = []
    for stage, (n_blocks, width) in enumerate(zip(cfg.stage_sizes, cfg.widths)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            cout = width * (4 if cfg.bottleneck else 1)
            bp = {}
            if cfg.bottleneck:
                bp["w1"] = _conv_init(next(keys), 1, 1, cin, width)
                bp["w2"] = _conv_init(next(keys), 3, 3, width, width)
                bp["w3"] = _conv_init(next(keys), 1, 1, width, cout)
                dims = (width, width, cout)
            else:
                bp["w1"] = _conv_init(next(keys), 3, 3, cin, width)
                bp["w2"] = _conv_init(next(keys), 3, 3, width, cout)
                dims = (width, cout)
            for i, dci in enumerate(dims):
                bp[f"s{i+1}"] = jnp.ones((dci,))
                bp[f"b{i+1}"] = jnp.zeros((dci,))
            if stride != 1 or cin != cout:
                bp["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
            blocks.append(bp)
            cin = cout
    p["blocks"] = blocks
    p["head"] = {"w": dense_init(next(keys), (cin, cfg.num_classes)),
                 "b": jnp.zeros((cfg.num_classes,))}
    return p


def num_layer_fns(cfg: ResNetConfig) -> int:
    """Chain length ``layer_fns`` produces (stem + blocks + head) — the
    ``n_layers`` a RematPlan for this model must be solved for."""
    return 2 + sum(cfg.stage_sizes)


def block_strides(cfg: ResNetConfig) -> list[int]:
    strides = []
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        for b in range(n_blocks):
            strides.append(2 if (b == 0 and stage > 0) else 1)
    return strides


def _block_fn(bp, cfg: ResNetConfig, stride: int):
    def fn(x):
        g = cfg.groups
        if cfg.bottleneck:
            h = jax.nn.relu(_group_norm(_conv(x, bp["w1"]), bp["s1"], bp["b1"], g))
            h = jax.nn.relu(_group_norm(_conv(h, bp["w2"], stride), bp["s2"], bp["b2"], g))
            h = _group_norm(_conv(h, bp["w3"]), bp["s3"], bp["b3"], g)
        else:
            h = jax.nn.relu(_group_norm(_conv(x, bp["w1"], stride), bp["s1"], bp["b1"], g))
            h = _group_norm(_conv(h, bp["w2"]), bp["s2"], bp["b2"], g)
        sc = _conv(x, bp["proj"], stride) if "proj" in bp else x
        return jax.nn.relu(h + sc)

    return fn


def layer_fns(params: dict, cfg: ResNetConfig) -> list[Callable]:
    """The sequential layer list ``checkpoint_sequential`` consumes."""
    fns: list[Callable] = [
        lambda x: jax.nn.relu(_group_norm(
            _conv(x, params["stem"]["w"], cfg.stem_stride),
            params["stem"]["s"], params["stem"]["b"], cfg.groups))
    ]
    fns += [_block_fn(bp, cfg, st)
           for bp, st in zip(params["blocks"], block_strides(cfg))]

    def head(x):
        x = x.mean((1, 2))
        return x @ params["head"]["w"] + params["head"]["b"]

    fns.append(head)
    return fns


def forward(params, cfg: ResNetConfig, images, *, remat=None,
            decode_backend: str | None = None):
    """images: f32 (B,H,W,C) or packed u32 (B/4,H,W,C) when decode_backend set.

    ``remat`` is the plan-bearing ``repro.core.checkpoint.CheckpointConfig``
    (None or ``enabled=False`` -> standard pipeline).  With ``remat.plan``
    set, S-C segments follow the planner's (possibly non-uniform)
    boundaries; otherwise layers are grouped uniformly, ``segment_size``
    layers per segment.  The old raw ``num_segments`` knob is gone — build
    an even plan with ``RematPlan.uniform(n_layers, k)`` if you need one.
    """
    x = images
    if decode_backend is not None:
        x = pack_ops.decode(x, backend=decode_backend)  # the E-D decode layer
    fns = layer_fns(params, cfg)
    if remat is not None and remat.enabled:
        from repro.core.checkpoint import checkpoint_sequential
        if remat.plan is not None:  # the plan carries its own policy
            return checkpoint_sequential(fns, plan=remat.plan,
                                         save_names=remat.save_names)(x)
        n_seg = -(-len(fns) // max(1, remat.segment_size))
        if n_seg > 1:
            return checkpoint_sequential(fns, n_seg, policy=remat.policy,
                                         save_names=remat.save_names)(x)
    for f in fns:
        x = f(x)
    return x


def loss_fn(params, cfg: ResNetConfig, images, labels, *, remat=None,
            decode_backend=None):
    logits = forward(params, cfg, images, remat=remat,
                     decode_backend=decode_backend)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return nll, {"acc": acc}
