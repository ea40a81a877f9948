"""Plain reference of a Llama-style decoder (the SmolLM family's layer
equations), and the weights the benchmark makes from its seed.

Per layer, with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g:

    h = x + Wo . attn(RoPE(Wq n1), RoPE(Wk n1), Wv n1),  n1 = RMSNorm(x)
    y = h + Wdown (silu(Wgate n2) * (Wup n2)),           n2 = RMSNorm(h)

attention causal over all earlier positions, scaled by 1/sqrt(head_dim),
grouped-query (each KV head serves ``heads / kv_heads`` consecutive query
heads), RoPE rotating dimension i with i + head_dim/2 (``rotate_half``) at
frequency theta^(-2i/head_dim).  Logits are RMSNorm(x) times the embedding
matrix (tied embeddings).  The forward pass runs over a whole sequence
with no cache, in float32 at ``Precision.HIGHEST``.

The weights are the benchmark's, not the program's: ``init_weights``
draws them from the seed in one jitted call, in the type they are served
in, in the plain layout below.  The serving loop hands the program a
reshaped copy (``program_config`` and ``program_params``, the only part of
this file that knows the program's names; the reference uses neither) and
draws the weights again after the window.

The control is the same forward pass with every linear layer computed in
int8 (weights per output channel, activations per token, both symmetric):
the precision below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    rope_theta: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        """From a configuration file holding the published config.json."""
        return cls(cfg["num_hidden_layers"], cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg.get("head_dim", cfg["hidden_size"]
                           // cfg["num_attention_heads"]),
                   cfg["intermediate_size"], cfg["vocab_size"],
                   cfg["rms_norm_eps"], cfg["rope_theta"])


def _key(seed: int):
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(d: Dims, key, dtype):
    L, D, F = d.layers, d.d_model, d.d_ff
    Hd, Kd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    ks = iter(jax.random.split(key, 11))

    def mat(*shape):
        return (0.02 * jax.random.normal(next(ks), shape)).astype(dtype)

    def gain(*shape):
        return (1.0 + 0.05 * jax.random.normal(next(ks), shape)).astype(dtype)

    return {"embed": mat(d.vocab, D),
            "wq": mat(L, D, Hd), "wk": mat(L, D, Kd), "wv": mat(L, D, Kd),
            "wo": mat(L, Hd, D),
            "w_gate": mat(L, D, F), "w_up": mat(L, D, F), "w_down": mat(L, F, D),
            "ln1": gain(L, D), "ln2": gain(L, D), "ln_f": gain(D)}


def init_weights(d: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight from ``seed``, on the default device, in one call."""
    return _init(d, _key(seed), jnp.dtype(dtype))


# -- the forward pass ----------------------------------------------------------


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [S, heads, hd]; rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _linear_f32(x, w):
    return x @ w


def _linear_int8(x, w):
    """W8A8: symmetric int8, weights per output column, activations per
    row, products summed exactly in int32."""
    ws = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    xs = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    wq = jnp.round(w / jnp.where(ws > 0, ws, 1)).astype(jnp.int8)
    xq = jnp.round(x / jnp.where(xs > 0, xs, 1)).astype(jnp.int8)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _forward(d: Dims, w: dict, tokens, linear):
    """tokens [S] int32 -> logits [S, vocab] float32."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    S = tokens.shape[0]
    H, K, hd = d.heads, d.kv_heads, d.head_dim
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens]

    def layer(x, p):
        n = _rms(x, p["ln1"], d.eps)
        q = _rope(linear(n, p["wq"]).reshape(S, H, hd), pos, d.rope_theta)
        k = _rope(linear(n, p["wk"]).reshape(S, K, hd), pos, d.rope_theta)
        v = linear(n, p["wv"]).reshape(S, K, hd)
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        h = x + linear(a.reshape(S, H * hd), p["wo"])
        n = _rms(h, p["ln2"], d.eps)
        f = jax.nn.silu(linear(n, p["w_gate"])) * linear(n, p["w_up"])
        return h + linear(f, p["w_down"]), None

    stacked = {k: w[k] for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                 "w_down", "ln1", "ln2")}
    x, _ = jax.lax.scan(layer, x, stacked)
    return linear(_rms(x, w["ln_f"], d.eps), w["embed"].T)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _gaps(d: Dims, w, tokens, control: bool, first, n):
    """At each position i that produced a served token tokens[i + 1]
    (first - 1 <= i < first - 1 + n): the reference's best logit minus
    its logit of that token; with ``control``, minus its logit of the token
    the control puts first there.  Returns, for each, the sum and the
    widest of those gaps and the number of positions whose gap is not 0."""
    with jax.default_matmul_precision("highest"):
        ref = _forward(d, w, tokens, _linear_f32)
        best = jnp.max(ref, -1)
        i = jnp.arange(tokens.shape[0])
        mask = (i >= first - 1) & (i < first - 1 + n)

        def stats(pick):
            g = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            return (jnp.sum(jnp.where(mask, g, 0.0)),
                    jnp.max(jnp.where(mask, g, -jnp.inf)),
                    jnp.sum(mask & (g > 0)).astype(jnp.float32))

        served = stats(jnp.roll(tokens, -1))
        if not control:
            return served, (jnp.float32(jnp.nan),) * 3
        return served, stats(jnp.argmax(_forward(d, w, tokens, _linear_int8),
                                        -1))


def logit_gaps(d: Dims, w: dict, prompt, served, width: int,
               control: bool = False) -> tuple[tuple, tuple]:
    """Over every served token of one request, the gap by which its
    reference logit lies below the reference's best: (sum, widest, number
    not the reference's best).  And the same for the token the control
    puts first at each of those positions (NaNs without ``control``).

    ``prompt + served`` is padded to ``width`` tokens, one compiled shape
    for every request: causal attention keeps the padding out of every
    position read."""
    seq = np.zeros(width, np.int32)
    toks = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served, np.int32)])
    if len(toks) > width:
        raise ValueError(f"request of {len(toks)} tokens > width {width}")
    seq[:len(toks)] = toks
    g, c = _gaps(d, w, jnp.asarray(seq), control, jnp.int32(len(prompt)),
                 jnp.int32(len(served)))
    return tuple(map(float, g)), tuple(map(float, c))


def compared(gaps: list, served: int) -> dict:
    """The numbers a run compares, from ``logit_gaps`` over a sample of
    requests holding ``served`` tokens in all: the mean gap, the widest,
    and the share of tokens that are not the reference's best."""
    return {"mean_logit_gap": sum(g[0] for g in gaps) / served,
            "max_logit_gap": max(g[1] for g in gaps),
            "not_best_share": sum(g[2] for g in gaps) / served}


def serve_flops(d: Dims, **work) -> float:
    """Model FLOPs of the serving work counted in a window."""
    from bench.flops import llama_serve_flops

    return llama_serve_flops(d, **work)


# -- the program's layout ------------------------------------------------------


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` for a
    configuration file holding a published Llama-style config.json."""
    d = Dims.of(cfg)
    return dict(name=cfg["name"], family="dense", num_layers=d.layers,
                d_model=d.d_model, num_heads=d.heads, num_kv_heads=d.kv_heads,
                d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
                block_pattern=("attn",), norm_eps=d.eps,
                rope_theta=d.rope_theta,
                tie_embeddings=cfg["tie_word_embeddings"],
                dtype=cfg["serve_dtype"], param_dtype=cfg["serve_dtype"])


def program_params(d: Dims, w: dict) -> dict:
    """The benchmark's weights in the program's layout (one jitted call
    that consumes them)."""
    def f(w):
        L, D, H, K, hd = d.layers, d.d_model, d.heads, d.kv_heads, d.head_dim
        return {"tok_embed": w["embed"], "final_scale": w["ln_f"],
                "s0_ln1_scale": w["ln1"], "s0_ln2_scale": w["ln2"],
                "s0_wq": w["wq"].reshape(L, D, H, hd),
                "s0_wk": w["wk"].reshape(L, D, K, hd),
                "s0_wv": w["wv"].reshape(L, D, K, hd),
                "s0_wo": w["wo"].reshape(L, H, hd, D),
                "s0_w_gate": w["w_gate"], "s0_w_up": w["w_up"],
                "s0_w_down": w["w_down"]}
    return jax.jit(f, donate_argnums=0)(w)
