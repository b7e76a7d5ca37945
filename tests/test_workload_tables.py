"""The layer tables of the paper's nine networks (``core/workloads.py``)
held to plain forward passes of the published networks.

Each network below is a plain ``jax.numpy`` float32 forward pass written
from its paper's layer structure; this module imports nothing of the
program's workload code except the tables it checks. Tracing a forward
with ``jax.make_jaxpr`` over ``ShapeDtypeStruct`` inputs at the published
shapes costs no memory. Every ``dot_general`` and
``conv_general_dilated`` of the trace is read as GEMMs (M, K, N):

* with a weight operand: one GEMM, M the input vectors (tokens, or
  output pixels times batch), K the reduction, N the outputs; a grouped
  convolution reduces over its group's channels, so a depthwise one is
  (H·W, k·k, C) as the tables encode it;
* without one (attention's QKᵀ and AV): one (M, K, N) per batch entry
  (head), activation times activation.

That multiset is compared with the table's rows. ``DEPARTURES`` names
every difference with its reason; an unlisted difference fails. The
tables are not changed here: the benchmark's reference
(``bench/reference/workloads.py``) holds the same rows, and a fix moves
both together.

A second part runs each forward at a small size on the CPU with seeded
random weights: the output has the network's shape and is finite, and
the GEMMs read from its trace are those the layer equations
(the ``*_gemms`` functions) give at that size, as they are at the published
size.
"""
import collections
import functools
import math
from typing import Callable, Dict, List, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.workloads import get_workload

Gemm = Tuple[int, int, int]


# ---------------------------------------------------------------------------
# weights: recorded on an abstract pass, then handed in as trace inputs
# ---------------------------------------------------------------------------

class Weights:
    """Hands a forward pass its weights in call order. Without values it
    records each shape and returns zeros (used under ``jax.eval_shape``,
    where the zeros are abstract)."""

    def __init__(self, values=None):
        self.values = values
        self.shapes: List[Tuple[int, ...]] = []

    def __call__(self, *shape):
        shape = tuple(int(s) for s in shape)
        i = len(self.shapes)
        self.shapes.append(shape)
        if self.values is None:
            return jnp.zeros(shape, jnp.float32)
        assert self.values[i].shape == shape, (i, shape)
        return self.values[i]


def _norm(x, axis=-1):
    """Normalization without parameters (batch norm at inference and
    layer norm alike scale elementwise: no GEMM)."""
    mu = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=axis, keepdims=True)
    return (x - mu) * lax.rsqrt(var + 1e-5)


def _conv(W, x, cin, cout, k, stride=1, pad=None, groups=1):
    """NHWC convolution with a (k, k, cin / groups, cout) weight."""
    p = k // 2 if pad is None else pad
    return lax.conv_general_dilated(
        x, W(k, k, cin // groups, cout), (stride, stride),
        [(p, p), (p, p)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _max_pool(x, k, s, pad=0):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, s, s, 1),
                             [(0, 0), (pad, pad), (pad, pad), (0, 0)])


def _avg_pool(x, k, s):
    return lax.reduce_window(x, 0.0, lax.add, (1, k, k, 1), (1, s, s, 1),
                             "VALID") / (k * k)


def _relu(x):
    return jnp.maximum(x, 0.0)


def _hswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def _attention(q, k, v, causal=False):
    """Multi-head attention on (S, H, Dh) operands."""
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = q.shape[0]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e9)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


def _heads(x, h):
    return x.reshape(x.shape[0], h, -1)


# ---------------------------------------------------------------------------
# the networks, each from its paper; ``c`` holds the sizes
# ---------------------------------------------------------------------------

def resnet(x, W, c):
    """ResNet v1 (arXiv:1512.03385, Table 1): a 7x7/2 stem and 3x3/2 max
    pool, four stages; a stage's first block strides 2 (after the first
    stage) in its first convolution, a 1x1 projection where the shape
    changes (option B)."""
    x = _relu(_norm(_conv(W, x, 3, c["stem"], 7, 2)))
    x = _max_pool(x, 3, 2, 1)
    cin = c["stem"]
    for stage, (nblk, width) in enumerate(zip(c["blocks"], c["widths"])):
        cout = width * (4 if c["bottleneck"] else 1)
        for b in range(nblk):
            s = 2 if b == 0 and stage > 0 else 1
            if c["bottleneck"]:
                h = _relu(_norm(_conv(W, x, cin, width, 1, s)))
                h = _relu(_norm(_conv(W, h, width, width, 3)))
                h = _norm(_conv(W, h, width, cout, 1))
            else:
                h = _relu(_norm(_conv(W, x, cin, width, 3, s)))
                h = _norm(_conv(W, h, width, width, 3))
            if s != 1 or cin != cout:
                x = _norm(_conv(W, x, cin, cout, 1, s))
            x = _relu(h + x)
            cin = cout
    return jnp.mean(x, axis=(1, 2)) @ W(cin, c["classes"])


def vgg(x, W, c):
    """VGG configuration D (arXiv:1409.1556): 3x3 convolutions, five 2x2
    max pools, three fully connected layers."""
    cin = 3
    for v in c["convs"]:
        if v == "M":
            x = _max_pool(x, 2, 2)
        else:
            x = _relu(_conv(W, x, cin, v, 3))
            cin = v
    x = x.reshape(x.shape[0], -1)
    for n in c["fc"][:-1]:
        x = _relu(x @ W(x.shape[1], n))
    return x @ W(x.shape[1], c["fc"][-1])


def alexnet(x, W, c):
    """AlexNet as one tower (arXiv:1404.5997, as torchvision builds it):
    11x11/4, 5x5 and three 3x3 convolutions, 3x3/2 max pools, three fully
    connected layers."""
    ch = c["channels"]
    x = _max_pool(_relu(_conv(W, x, 3, ch[0], 11, 4, 2)), 3, 2)
    x = _max_pool(_relu(_conv(W, x, ch[0], ch[1], 5)), 3, 2)
    x = _relu(_conv(W, x, ch[1], ch[2], 3))
    x = _relu(_conv(W, x, ch[2], ch[3], 3))
    x = _max_pool(_relu(_conv(W, x, ch[3], ch[4], 3)), 3, 2)
    x = x.reshape(x.shape[0], -1)
    for n in c["fc"][:-1]:
        x = _relu(x @ W(x.shape[1], n))
    return x @ W(x.shape[1], c["fc"][-1])


def _divisible(v, d=8):
    n = max(d, int(v + d / 2) // d * d)
    return n + d if n < 0.9 * v else n


# MobileNetV3-Large (arXiv:1905.02244, Table 1): kernel, expansion,
# output, squeeze-and-excite, stride
MBV3_LARGE = ((3, 16, 16, False, 1), (3, 64, 24, False, 2),
              (3, 72, 24, False, 1), (5, 72, 40, True, 2),
              (5, 120, 40, True, 1), (5, 120, 40, True, 1),
              (3, 240, 80, False, 2), (3, 200, 80, False, 1),
              (3, 184, 80, False, 1), (3, 184, 80, False, 1),
              (3, 480, 112, True, 1), (3, 672, 112, True, 1),
              (5, 672, 160, True, 2), (5, 960, 160, True, 1),
              (5, 960, 160, True, 1))


def mobilenetv3(x, W, c):
    """MobileNetV3-Large: inverted residual blocks (1x1 expansion where
    the width grows, depthwise kxk that carries the stride, optional
    squeeze-and-excite on the expanded width reduced 4x, 1x1
    projection), a 1x1 convolution to 960, pooling, two fully connected
    layers."""
    x = _hswish(_norm(_conv(W, x, 3, 16, 3, 2)))
    cin = 16
    for k, exp, cout, se, s in MBV3_LARGE:
        h = x
        if exp != cin:
            h = _hswish(_norm(_conv(W, h, cin, exp, 1)))
        h = _hswish(_norm(_conv(W, h, exp, exp, k, s, groups=exp)))
        if se:
            sq = _divisible(exp // 4)
            z = _relu(jnp.mean(h, axis=(1, 2)) @ W(exp, sq))
            z = jnp.clip((z @ W(sq, exp)) / 6.0 + 0.5, 0.0, 1.0)
            h = h * z[:, None, None, :]
        h = _norm(_conv(W, h, exp, cout, 1))
        x = x + h if s == 1 and cin == cout else h
        cin = cout
    x = _hswish(_norm(_conv(W, x, cin, 960, 1)))
    x = _hswish(jnp.mean(x, axis=(1, 2)) @ W(960, 1280))
    return x @ W(1280, c["classes"])


def densenet(x, W, c):
    """DenseNet-BC (arXiv:1608.06993, Table 1): a 7x7/2 stem and max
    pool, dense blocks of 1x1 (4k) then 3x3 (k) convolutions, each
    layer's output concatenated; between blocks a 1x1 convolution
    halving the channels, then a 2x2 average pool."""
    g = c["growth"]
    x = _max_pool(_relu(_norm(_conv(W, x, 3, c["stem"], 7, 2))), 3, 2, 1)
    ch = c["stem"]
    for i, n in enumerate(c["blocks"]):
        for _ in range(n):
            h = _conv(W, _relu(_norm(x)), ch, 4 * g, 1)
            h = _conv(W, _relu(_norm(h)), 4 * g, g, 3)
            x = jnp.concatenate([x, h], axis=-1)
            ch += g
        if i < len(c["blocks"]) - 1:
            x = _avg_pool(_conv(W, _relu(_norm(x)), ch, ch // 2, 1), 2, 2)
            ch //= 2
    return jnp.mean(_relu(_norm(x)), axis=(1, 2)) @ W(ch, c["classes"])


def mobilebert(ids, W, c):
    """MobileBERT (arXiv:2004.02984): a trigram embedding of width e
    widened to d, then layers of: a bottleneck d -> b for the block's
    input and, keys and queries sharing it, a second d -> b feeding Q
    and K, with V from the d-wide input; attention, its output dense;
    stacked b -> 4b -> b feed-forward networks; the output bottleneck
    b -> d. The masked-LM head transforms d -> d and decodes against
    the embedding table widened to d."""
    d, b, e, h, V = c["d"], c["b"], c["e"], c["heads"], c["vocab"]
    emb = W(V, e)
    t = jnp.take(emb, ids, axis=0)
    z = jnp.zeros((1, e), t.dtype)
    tri = jnp.concatenate([jnp.concatenate([t[1:], z]), t,
                           jnp.concatenate([z, t[:-1]])], axis=-1)
    x = tri @ W(3 * e, d)
    for _ in range(c["layers"]):
        inp = _norm(x @ W(d, b))
        shared = _norm(x @ W(d, b))
        q = _heads(shared @ W(b, b), h)
        k = _heads(shared @ W(b, b), h)
        v = _heads(x @ W(d, b), h)
        a = _attention(q, k, v).reshape(x.shape[0], b)
        y = _norm(a @ W(b, b) + inp)
        for _ in range(c["ffn"]):
            y = _norm(_relu(y @ W(b, 4 * b)) @ W(4 * b, b) + y)
        x = _norm(y @ W(b, d) + x)
    x = _norm(_relu(x @ W(d, d)))
    dec = jnp.concatenate([emb.T, W(d - e, V)], axis=0)
    return x @ dec


def vit(img, W, c):
    """ViT (arXiv:2010.11929): p x p patches embedded by a strided
    convolution, a class token, pre-norm encoder layers (fused QKV,
    attention, output projection, a 4d MLP), the classifier on the class
    token."""
    d, h, p = c["d"], c["heads"], c["patch"]
    x = _conv(W, img, 3, d, p, p, 0)
    x = x.reshape(-1, d)
    x = jnp.concatenate([W(1, d), x]) + W(x.shape[0] + 1, d)
    for _ in range(c["layers"]):
        qkv = _norm(x) @ W(d, 3 * d)
        q, k, v = (_heads(t, h) for t in jnp.split(qkv, 3, axis=-1))
        x = x + _attention(q, k, v).reshape(x.shape) @ W(d, d)
        x = x + jax.nn.gelu(_norm(x) @ W(d, c["mlp"])) @ W(c["mlp"], d)
    return _norm(x[0]) @ W(d, c["classes"])


def gpt2(ids, W, c):
    """GPT-2 (Radford et al. 2019): token and position embeddings,
    pre-norm decoder layers (fused QKV, causal attention, output
    projection, a 4d MLP), the unembedding tied to the token
    embedding."""
    d, h = c["d"], c["heads"]
    wte = W(c["vocab"], d)
    x = jnp.take(wte, ids, axis=0) + W(c["ctx"], d)[:ids.shape[0]]
    for _ in range(c["layers"]):
        qkv = _norm(x) @ W(d, 3 * d)
        q, k, v = (_heads(t, h) for t in jnp.split(qkv, 3, axis=-1))
        x = x + _attention(q, k, v, causal=True).reshape(x.shape) @ W(d, d)
        x = x + jax.nn.gelu(_norm(x) @ W(d, 4 * d)) @ W(4 * d, d)
    return _norm(x) @ wte.T


# ---------------------------------------------------------------------------
# the layer equations: the GEMMs each network computes at sizes ``c``
# ---------------------------------------------------------------------------

def _out(hw, k, s, p):
    return (hw + 2 * p - k) // s + 1


def resnet_gemms(c):
    hw = _out(c["res"], 7, 2, 3)
    G = [(hw * hw, 3 * 49, c["stem"])]
    hw = _out(hw, 3, 2, 1)
    cin = c["stem"]
    for stage, (nblk, width) in enumerate(zip(c["blocks"], c["widths"])):
        cout = width * (4 if c["bottleneck"] else 1)
        for b in range(nblk):
            if b == 0 and stage > 0:
                hw = _out(hw, 1, 2, 0)
            m = hw * hw
            if c["bottleneck"]:
                G += [(m, cin, width), (m, width * 9, width),
                      (m, width, cout)]
            else:
                G += [(m, cin * 9, width), (m, width * 9, width)]
            if (b == 0 and stage > 0) or cin != cout:
                G.append((m, cin, cout))
            cin = cout
    return G + [(1, cin, c["classes"])]


def vgg_gemms(c):
    hw, cin, G = c["res"], 3, []
    for v in c["convs"]:
        if v == "M":
            hw //= 2
        else:
            G.append((hw * hw, cin * 9, v))
            cin = v
    k = hw * hw * cin
    for n in c["fc"]:
        G.append((1, k, n))
        k = n
    return G


def alexnet_gemms(c):
    ch = c["channels"]
    hw = _out(c["res"], 11, 4, 2)
    G = [(hw * hw, 3 * 121, ch[0])]
    hw = _out(hw, 3, 2, 0)
    G.append((hw * hw, ch[0] * 25, ch[1]))
    hw = _out(hw, 3, 2, 0)
    G += [(hw * hw, ch[1] * 9, ch[2]), (hw * hw, ch[2] * 9, ch[3]),
          (hw * hw, ch[3] * 9, ch[4])]
    k = _out(hw, 3, 2, 0) ** 2 * ch[4]
    for n in c["fc"]:
        G.append((1, k, n))
        k = n
    return G


def mobilenetv3_gemms(c):
    hw = _out(c["res"], 3, 2, 1)
    G = [(hw * hw, 27, 16)]
    cin = 16
    for k, exp, cout, se, s in MBV3_LARGE:
        if exp != cin:
            G.append((hw * hw, cin, exp))
        hw = _out(hw, k, s, k // 2)
        G.append((hw * hw, k * k, exp))
        if se:
            G += [(1, exp, _divisible(exp // 4)), (1, _divisible(exp // 4),
                                                   exp)]
        G.append((hw * hw, exp, cout))
        cin = cout
    return G + [(hw * hw, cin, 960), (1, 960, 1280),
                (1, 1280, c["classes"])]


def densenet_gemms(c):
    g = c["growth"]
    hw = _out(c["res"], 7, 2, 3)
    G = [(hw * hw, 3 * 49, c["stem"])]
    hw = _out(hw, 3, 2, 1)
    ch = c["stem"]
    for i, n in enumerate(c["blocks"]):
        for _ in range(n):
            G += [(hw * hw, ch, 4 * g), (hw * hw, 4 * g * 9, g)]
            ch += g
        if i < len(c["blocks"]) - 1:
            G.append((hw * hw, ch, ch // 2))
            ch, hw = ch // 2, hw // 2
    return G + [(1, ch, c["classes"])]


def _attention_gemms(s, dh, heads, layers):
    return [(s, dh, s), (s, s, dh)] * heads * layers


def mobilebert_gemms(c):
    s, d, b, e = c["seq"], c["d"], c["b"], c["e"]
    per = [(s, d, b), (s, d, b), (s, b, b), (s, b, b), (s, d, b),
           (s, b, b)] + [(s, b, 4 * b), (s, 4 * b, b)] * c["ffn"] + \
        [(s, b, d)]
    return ([(s, 3 * e, d)] + per * c["layers"]
            + _attention_gemms(s, b // c["heads"], c["heads"], c["layers"])
            + [(s, d, d), (s, d, c["vocab"])])


def vit_gemms(c):
    d, n = c["d"], (c["res"] // c["patch"]) ** 2
    s = n + 1
    per = [(s, d, 3 * d), (s, d, d), (s, d, c["mlp"]), (s, c["mlp"], d)]
    return ([(n, 3 * c["patch"] ** 2, d)] + per * c["layers"]
            + _attention_gemms(s, d // c["heads"], c["heads"], c["layers"])
            + [(1, d, c["classes"])])


def gpt2_gemms(c):
    s, d = c["seq"], c["d"]
    per = [(s, d, 3 * d), (s, d, d), (s, d, 4 * d), (s, 4 * d, d)]
    return (per * c["layers"]
            + _attention_gemms(s, d // c["heads"], c["heads"], c["layers"])
            + [(s, d, c["vocab"])])


# ---------------------------------------------------------------------------
# sizes: published, and small enough to run on the CPU
# ---------------------------------------------------------------------------

RESNET18 = dict(res=224, stem=64, blocks=(2, 2, 2, 2),
                widths=(64, 128, 256, 512), bottleneck=False, classes=1000)
RESNET50 = dict(RESNET18, blocks=(3, 4, 6, 3), bottleneck=True)
VGG16 = dict(res=224, convs=(64, 64, "M", 128, 128, "M", 256, 256, 256,
                             "M", 512, 512, 512, "M", 512, 512, 512, "M"),
             fc=(4096, 4096, 1000))
ALEXNET = dict(res=224, channels=(64, 192, 384, 256, 256),
               fc=(4096, 4096, 1000))
MBV3 = dict(res=224, classes=1000)
DENSENET201 = dict(res=224, stem=64, growth=32, blocks=(6, 12, 48, 32),
                   classes=1000)
MOBILEBERT = dict(seq=128, d=512, b=128, e=128, heads=4, ffn=4, layers=24,
                  vocab=30522)
VIT_B16 = dict(res=224, patch=16, d=768, heads=12, mlp=3072, layers=12,
               classes=1000)
GPT2_MEDIUM = dict(seq=1024, ctx=1024, d=1024, heads=16, layers=24,
                   vocab=50257)

# name: (forward, layer equations, published sizes, small sizes)
NETWORKS: Dict[str, Tuple[Callable, Callable, Dict, Dict]] = {
    "resnet18": (resnet, resnet_gemms, RESNET18,
                 dict(RESNET18, res=32, stem=8, widths=(8, 16, 32, 64),
                      classes=10)),
    "resnet50": (resnet, resnet_gemms, RESNET50,
                 dict(RESNET50, res=32, stem=8, widths=(4, 8, 16, 32),
                      classes=10)),
    "vgg16": (vgg, vgg_gemms, VGG16,
              dict(res=32, convs=(4, 4, "M", 8, 8, "M", 16, 16, 16, "M",
                                  32, 32, 32, "M", 32, 32, 32, "M"),
                   fc=(64, 64, 10))),
    "alexnet": (alexnet, alexnet_gemms, ALEXNET,
                dict(res=67, channels=(8, 24, 48, 32, 32), fc=(64, 64, 10))),
    "mobilenetv3": (mobilenetv3, mobilenetv3_gemms, MBV3,
                    dict(res=32, classes=10)),
    "densenet201": (densenet, densenet_gemms, DENSENET201,
                    dict(res=32, stem=8, growth=4, blocks=(2, 2, 2, 2),
                         classes=10)),
    "mobilebert": (mobilebert, mobilebert_gemms, MOBILEBERT,
                   dict(seq=8, d=32, b=16, e=8, heads=2, ffn=2, layers=2,
                        vocab=50)),
    "vit_b16": (vit, vit_gemms, VIT_B16,
                dict(res=32, patch=8, d=32, heads=2, mlp=64, layers=2,
                     classes=10)),
    "gpt2_medium": (gpt2, gpt2_gemms, GPT2_MEDIUM,
                    dict(seq=16, ctx=16, d=32, heads=2, layers=2,
                         vocab=50)),
}
PAPER_9 = ("resnet18", "vgg16", "alexnet", "mobilenetv3", "mobilebert",
           "densenet201", "resnet50", "vit_b16", "gpt2_medium")


def _input(name, c):
    if "seq" in c:
        return jax.ShapeDtypeStruct((c["seq"],), jnp.int32)
    return jax.ShapeDtypeStruct((1, c["res"], c["res"], 3), jnp.float32)


def _out_shape(name, c):
    if "seq" in c:
        return (c["seq"], c["vocab"])
    classes = c["fc"][-1] if "fc" in c else c["classes"]
    # ViT classifies its one image's class token
    return (classes,) if name == "vit_b16" else (1, classes)


# ---------------------------------------------------------------------------
# reading GEMMs from a trace
# ---------------------------------------------------------------------------

# primitives that pass a weight on as a weight (a layout, not a product)
_LAYOUT = {"transpose", "reshape", "convert_element_type", "squeeze",
           "expand_dims", "concatenate", "copy"}


def _prod(xs):
    return int(np.prod([int(x) for x in xs], dtype=np.int64))


def _dot(e, w_lhs: bool, w_rhs: bool) -> List[Gemm]:
    ls, rs = e.invars[0].aval.shape, e.invars[1].aval.shape
    (lc, rc), (lb, rb) = e.params["dimension_numbers"]
    k = _prod(ls[i] for i in lc)
    batch = _prod(ls[i] for i in lb)
    lfree = _prod(ls[i] for i in range(len(ls)) if i not in lc + lb)
    rfree = _prod(rs[i] for i in range(len(rs)) if i not in rc + rb)
    if w_rhs:
        return [(lfree, k, rfree)] * batch
    if w_lhs:
        return [(rfree, k, lfree)] * batch
    # activation x activation: the same product either way round, read
    # with the longer free side as M
    return [(max(lfree, rfree), k, min(lfree, rfree))] * batch


def _conv_gemm(e) -> Gemm:
    dn = e.params["dimension_numbers"]
    rs = e.invars[1].aval.shape
    os_ = e.outvars[0].aval.shape
    m = os_[dn.out_spec[0]] * _prod(os_[i] for i in dn.out_spec[2:])
    k = rs[dn.rhs_spec[1]] * _prod(rs[i] for i in dn.rhs_spec[2:])
    return (m, k, rs[dn.rhs_spec[0]])


def _sub_jaxprs(e):
    for v in e.params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v


def read_gemms(jaxpr, weights) -> Tuple[List[Gemm], List[Gemm]]:
    """(GEMMs with a weight operand, activation x activation GEMMs) of a
    jaxpr whose variables in ``weights`` hold weights."""
    with_w, act = [], []

    def is_w(v):
        return not isinstance(v, jcore.Literal) and v in weights

    for e in jaxpr.eqns:
        name = e.primitive.name
        if name == "dot_general":
            wl, wr = is_w(e.invars[0]), is_w(e.invars[1])
            (with_w if wl or wr else act).extend(_dot(e, wl, wr))
        elif name == "conv_general_dilated":
            assert is_w(e.invars[1]), "a convolution without a weight"
            with_w.append(_conv_gemm(e))
        elif name in _LAYOUT and e.invars and all(map(is_w, e.invars)):
            weights = weights | set(e.outvars)
        for sub in _sub_jaxprs(e):
            inner = {s for s, v in zip(sub.invars, e.invars) if is_w(v)}
            a, b = read_gemms(sub, inner)
            with_w += a
            act += b
    return with_w, act


@functools.lru_cache(maxsize=None)
def traced(name: str, small: bool = False):
    """The network's trace at its published (or small) sizes: (jaxpr,
    weight shapes)."""
    fwd, _, pub, sm = NETWORKS[name]
    c = sm if small else pub
    x = _input(name, c)
    rec = Weights()
    jax.eval_shape(lambda a: fwd(a, rec, c), x)
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in rec.shapes]
    closed = jax.make_jaxpr(lambda ws, a: fwd(a, Weights(ws), c))(shapes, x)
    return closed, rec.shapes


def network_gemms(name: str, small: bool = False) -> Tuple[List, List]:
    closed, shapes = traced(name, small)
    return read_gemms(closed.jaxpr, set(closed.jaxpr.invars[:len(shapes)]))


def _count(gemms) -> collections.Counter:
    return collections.Counter(tuple(int(v) for v in g) for g in gemms)


def table_gemms(name: str) -> collections.Counter:
    return _count(get_workload(name).layers.astype(np.int64))


# ---------------------------------------------------------------------------
# where the tables depart from the networks
# ---------------------------------------------------------------------------

ATTN = ("attention's {} product: activation times activation, no weight "
        "to hold in a crossbar; the export leaves it out")
QK = ATTN.format("QK^T")
AV = ATTN.format("AV")
MBV3_EXP = ("a stride-2 block's 1x1 expansion runs before its depthwise "
            "convolution, at the input resolution; the table puts it at "
            "the output resolution")
MBV3_SE = ("squeeze-and-excite fully connected layers (to a quarter of "
           "the expanded width, rounded to 8, and back) on the pooled "
           "vector; the table leaves them out")
DN_TRANS = ("a transition's 1x1 convolution runs before its 2x2 average "
            "pool; the table puts it at the pooled resolution")
MB_QKV = ("the table fuses Q, K, V as one 128 -> 384 GEMM on the block's "
          "bottleneck; the network computes Q and K from a second, shared "
          "512 -> 128 bottleneck and V from the 512-wide input")
VIT_HEAD = ("the classifier runs on the class token alone (M=1); the "
            "table runs it on all 197 tokens")

# name -> [(side, (M, K, N), count, reason)]: "network" where only the
# forward pass has the GEMM, "table" where only the table has it
DEPARTURES: Dict[str, List[Tuple[str, Gemm, int, str]]] = {
    "resnet18": [],
    "resnet50": [],
    "vgg16": [],
    "alexnet": [],
    "mobilenetv3": [
        ("network", (112 * 112, 16, 64), 1, MBV3_EXP),
        ("table", (56 * 56, 16, 64), 1, MBV3_EXP),
        ("network", (56 * 56, 24, 72), 1, MBV3_EXP),
        ("table", (28 * 28, 24, 72), 1, MBV3_EXP),
        ("network", (28 * 28, 40, 240), 1, MBV3_EXP),
        ("table", (14 * 14, 40, 240), 1, MBV3_EXP),
        ("network", (14 * 14, 112, 672), 1, MBV3_EXP),
        ("table", (7 * 7, 112, 672), 1, MBV3_EXP),
        ("network", (1, 72, 24), 1, MBV3_SE),
        ("network", (1, 24, 72), 1, MBV3_SE),
        ("network", (1, 120, 32), 2, MBV3_SE),
        ("network", (1, 32, 120), 2, MBV3_SE),
        ("network", (1, 480, 120), 1, MBV3_SE),
        ("network", (1, 120, 480), 1, MBV3_SE),
        ("network", (1, 672, 168), 2, MBV3_SE),
        ("network", (1, 168, 672), 2, MBV3_SE),
        ("network", (1, 960, 240), 2, MBV3_SE),
        ("network", (1, 240, 960), 2, MBV3_SE),
    ],
    "densenet201": [
        ("network", (56 * 56, 256, 128), 1, DN_TRANS),
        ("table", (28 * 28, 256, 128), 1, DN_TRANS),
        ("network", (28 * 28, 512, 256), 1, DN_TRANS),
        ("table", (14 * 14, 512, 256), 1, DN_TRANS),
        ("network", (14 * 14, 1792, 896), 1, DN_TRANS),
        ("table", (7 * 7, 1792, 896), 1, DN_TRANS),
    ],
    "mobilebert": [
        ("network", (128, 384, 512), 1,
         "the trigram embedding's 3 x 128 -> 512 transformation; the "
         "table starts at the first block"),
        ("table", (128, 128, 384), 24, MB_QKV),
        ("network", (128, 512, 128), 48, MB_QKV),
        ("network", (128, 128, 128), 48, MB_QKV),
        ("network", (128, 512, 512), 1,
         "the masked-LM head's 512 -> 512 transform before the decoder; "
         "the table keeps only the decoder"),
        ("network", (128, 32, 128), 24 * 4, QK),
        ("network", (128, 128, 32), 24 * 4, AV),
    ],
    "vit_b16": [
        ("table", (197, 768, 1000), 1, VIT_HEAD),
        ("network", (1, 768, 1000), 1, VIT_HEAD),
        ("network", (197, 64, 197), 12 * 12, QK),
        ("network", (197, 197, 64), 12 * 12, AV),
    ],
    "gpt2_medium": [
        ("network", (1024, 64, 1024), 24 * 16, QK),
        ("network", (1024, 1024, 64), 24 * 16, AV),
    ],
}


def _expected(name, side) -> collections.Counter:
    return collections.Counter(
        {g: n for s, g, n, _ in DEPARTURES[name] if s == side})


def test_every_paper_network_is_checked():
    assert set(NETWORKS) == set(DEPARTURES) == set(PAPER_9)
    from repro.core.workloads import PAPER_9 as program_set
    assert set(program_set) == set(PAPER_9)


@pytest.mark.parametrize("name", PAPER_9)
def test_table_matches_the_network_but_for_named_departures(name):
    """At the published shapes the table's rows are the network's GEMMs,
    but for the differences ``DEPARTURES`` names."""
    with_w, act = network_gemms(name)
    net, table = _count(with_w + act), table_gemms(name)
    only_net, only_table = net - table, table - net
    assert only_net == _expected(name, "network"), (
        "GEMMs of the network missing from the table, unlisted or "
        f"listed wrongly: {dict(only_net - _expected(name, 'network'))}, "
        f"listed but absent: {dict(_expected(name, 'network') - only_net)}")
    assert only_table == _expected(name, "table"), (
        f"rows of the table the network lacks: {dict(only_table)}")


@pytest.mark.parametrize("name", PAPER_9)
@pytest.mark.parametrize("small", [False, True], ids=["published", "small"])
def test_trace_reads_the_layer_equations(name, small):
    """The GEMMs read from the trace are those the network's layer
    equations give, at the published sizes and at the small ones."""
    _, eqs, pub, sm = NETWORKS[name]
    with_w, act = network_gemms(name, small)
    assert _count(with_w + act) == _count(eqs(sm if small else pub))


@pytest.mark.parametrize("name", PAPER_9)
def test_small_forward_runs(name):
    """At the small sizes, with seeded random weights, the forward pass
    runs on the CPU to an output of the network's shape, and its weight
    GEMMs' MACs are those of the layer equations' weight GEMMs."""
    fwd, eqs, _, c = NETWORKS[name]
    _, shapes = traced(name, small=True)
    keys = jax.random.split(jax.random.PRNGKey(20260415), len(shapes) + 1)
    ws = [jax.random.normal(k, s, jnp.float32)
          / math.sqrt(_prod(s[:-1]) if len(s) > 1 else 1.0)
          for k, s in zip(keys[1:], shapes)]
    x = _input(name, c)
    if x.dtype == jnp.int32:
        a = jax.random.randint(keys[0], x.shape, 0, c["vocab"])
    else:
        a = jax.random.normal(keys[0], x.shape, jnp.float32)
    y = jax.jit(lambda ws, a: fwd(a, Weights(ws), c))(ws, a)
    assert y.shape == _out_shape(name, c)
    assert bool(jnp.all(jnp.isfinite(y)))
    with_w, act = network_gemms(name, small=True)
    assert len(act) == (2 * c["heads"] * c["layers"] if "heads" in c
                        else 0)
    eq_w = _count(eqs(c)) - _count(act)
    assert sum(_prod(g) for g in with_w) == \
        sum(_prod(g) * n for g, n in eq_w.items())


def test_gpt2_medium_activation_share():
    """GPT-2 medium's attention products (QK^T and AV, left out of its
    table) as a share of all its MACs at seq 1024."""
    with_w, act = network_gemms("gpt2_medium")
    w_macs = sum(_prod(g) for g in with_w)
    a_macs = sum(_prod(g) for g in act)
    assert w_macs == get_workload("gpt2_medium").macs
    assert a_macs == 24 * 2 * 1024 * 1024 * 1024
    assert a_macs / (w_macs + a_macs) == pytest.approx(0.12465, abs=1e-5)
