"""Plain reference of a Granite 4.0-H decoder (``granitemoehybrid`` with no
experts: Mamba-2 mixers and NoPE grouped-query attention, each layer with
its own MLP), and the weights the benchmark makes from its seed.

Per layer l, with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g and the
residual multiplier r:

    h = x + r * mixer_l(RMSNorm_1(x))
    y = h + r * Wdown (silu(Wgate n2) * (Wup n2)),   n2 = RMSNorm_2(h)

``mixer_l`` is attention where ``layer_types[l]`` is "attention": causal
over every earlier position, scores q.k times ``attention_multiplier``,
grouped-query, no positional encoding.  Elsewhere it is the Mamba-2 mixer
(n_groups 1), as the published ``BambaMixer``/``GraniteMoeHybridMambaLayer``
computes it: ``in_proj`` gives (z, xBC, dt); xBC goes through a causal
depthwise conv of width ``mamba_d_conv`` with bias and a SiLU, and splits
into (x, B, C); dt = softplus(dt + dt_bias), A = -exp(A_log); then over
time t, for every head, with a state S of [head_dim, d_state] from 0:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t

and the output is ``out_proj`` of RMSNorm(y * silu(z)) over the whole
inner width.  The recurrence runs token by token (``lax.scan``), not in
chunks.  The embeddings are multiplied by ``embedding_multiplier``; the
logits are RMSNorm(x) times the embedding matrix (tied), divided by
``logits_scaling``.  The forward pass runs over a whole sequence with no
cache, in float32 at ``Precision.HIGHEST``.

Departures from the published model: the weights are random (drawn from
the seed as below, not trained); the embedding is drawn at 0.02 /
``embedding_multiplier`` so that the scaled embeddings have the scale of
the other matrices (at 0.02 the tied head, after the x12, puts each input
token itself first at every position, whatever the layers compute); the
conv kernel and bias are drawn as
PyTorch's ``Conv1d`` default (uniform in +-1/sqrt(width)), A_log as
log U[1, 16], dt_bias as softplus^-1 of a log-uniform dt in [1e-3, 1e-1]
(Mamba-2's initialization), D as 1; the MLP's fused ``input_linear`` is
held as its two halves (gate, up).

The control is the same forward pass with every linear layer computed in
int8 (weights per output channel, activations per token, both symmetric):
the precision below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.llama import (_key, _linear_f32, _linear_int8, _rms,
                                   compared)

__all__ = ["Dims", "init_weights", "logit_gaps", "compared", "serve_flops",
           "ssd_step_work", "program_config", "program_params"]


@dataclasses.dataclass(frozen=True)
class Dims:
    layer_types: tuple          # "mamba" | "attention", one per layer
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    d_conv: int
    chunk: int
    rope: bool
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        """From a configuration file holding the published config.json."""
        if cfg["mamba_n_groups"] != 1:
            raise ValueError("the program's Mamba-2 mixer has one group")
        if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != \
                cfg["mamba_expand"] * cfg["hidden_size"]:
            raise ValueError("mamba heads x head size != expand x hidden")
        types = tuple(cfg["layer_types"])
        if len(types) != cfg["num_hidden_layers"]:
            raise ValueError("layer_types does not name every layer")
        return cls(types, cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"],
                   cfg.get("head_dim", cfg["hidden_size"]
                           // cfg["num_attention_heads"]),
                   cfg["intermediate_size"], cfg["vocab_size"],
                   cfg["rms_norm_eps"], cfg["mamba_n_heads"],
                   cfg["mamba_d_head"], cfg["mamba_d_state"],
                   cfg["mamba_d_conv"], cfg["mamba_chunk_size"],
                   cfg["position_embedding_type"] != "nope",
                   float(cfg["embedding_multiplier"]),
                   float(cfg["attention_multiplier"]),
                   float(cfg["residual_multiplier"]),
                   float(cfg["logits_scaling"]))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.d_state

    def index(self, kind: str) -> list[int]:
        """The layers of ``kind``, in order."""
        return [i for i, t in enumerate(self.layer_types) if t == kind]


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(d: Dims, key, dtype):
    L, D, F = d.layers, d.d_model, d.d_ff
    Lm, La = len(d.index("mamba")), len(d.index("attention"))
    Hd, Kd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    di, nh, C = d.d_inner, d.ssm_heads, d.conv_dim
    ks = iter(jax.random.split(key, 20))

    def mat(*shape):
        return (0.02 * jax.random.normal(next(ks), shape)).astype(dtype)

    def gain(*shape):
        return (1.0 + 0.05 * jax.random.normal(next(ks), shape)).astype(dtype)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    bound = 1.0 / math.sqrt(d.d_conv)
    dt = jnp.exp(uniform(math.log(1e-3), math.log(1e-1), Lm, nh))
    embed = mat(d.vocab, D) / d.embedding_multiplier
    return {"embed": embed.astype(dtype), "ln1": gain(L, D), "ln2": gain(L, D),
            "w_gate": mat(L, D, F), "w_up": mat(L, D, F),
            "w_down": mat(L, F, D), "ln_f": gain(D),
            "wq": mat(La, D, Hd), "wk": mat(La, D, Kd), "wv": mat(La, D, Kd),
            "wo": mat(La, Hd, D),
            "in_proj": mat(Lm, D, di + C + nh),
            "conv_w": uniform(-bound, bound, Lm, d.d_conv, C).astype(dtype),
            "conv_b": uniform(-bound, bound, Lm, C).astype(dtype),
            "A_log": jnp.log(uniform(1.0, 16.0, Lm, nh)).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "D": jnp.ones((Lm, nh), dtype),
            "norm": gain(Lm, di), "out_proj": mat(Lm, di, D)}


def init_weights(d: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight from ``seed``, on the default device, in one call."""
    return _init(d, _key(seed), jnp.dtype(dtype))


# -- the forward pass ----------------------------------------------------------


def _mamba(d: Dims, p: dict, n, linear):
    """The Mamba-2 mixer over a whole sequence n [S, D], token by token."""
    S = n.shape[0]
    di, ds, nh, hd = d.d_inner, d.d_state, d.ssm_heads, d.ssm_head_dim
    zxd = linear(n, p["in_proj"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + d.conv_dim], zxd[:, di + d.conv_dim:]
    pad = jnp.concatenate([jnp.zeros((d.d_conv - 1, d.conv_dim)), xbc])
    xbc = jax.nn.silu(sum(pad[j:j + S] * p["conv_w"][j]
                          for j in range(d.d_conv)) + p["conv_b"])
    x, B, C = xbc[:, :di].reshape(S, nh, hd), xbc[:, di:di + ds], xbc[:, di + ds:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(s, t):
        x_t, B_t, C_t, dt_t = t
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        return s, s @ C_t + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, ds)), (x, B, C, dt))
    y = _rms(y.reshape(S, di) * jax.nn.silu(z), p["norm"], d.eps)
    return linear(y, p["out_proj"])


def _attention(d: Dims, p: dict, n, linear):
    S = n.shape[0]
    H, K, hd = d.heads, d.kv_heads, d.head_dim
    if d.rope:
        raise NotImplementedError("this reference serves NoPE attention")
    q = linear(n, p["wq"]).reshape(S, H, hd)
    k = jnp.repeat(linear(n, p["wk"]).reshape(S, K, hd), H // K, axis=1)
    v = jnp.repeat(linear(n, p["wv"]).reshape(S, K, hd), H // K, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d.attention_multiplier
    pos = jnp.arange(S)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return linear(a.reshape(S, H * hd), p["wo"])


MAMBA_KEYS = ("in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm",
              "out_proj")
ATTN_KEYS = ("wq", "wk", "wv", "wo")
LAYER_KEYS = ("ln1", "ln2", "w_gate", "w_up", "w_down")


def _forward(d: Dims, w: dict, tokens, linear):
    """tokens [S] int32 -> logits [S, vocab] float32.  One loop over the
    layers; each casts its own weights to float32 where it runs."""
    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    kind = np.array([t == "attention" for t in d.layer_types])
    within = np.zeros(d.layers, np.int32)          # index in its kind's stack
    within[kind] = np.arange(kind.sum())
    within[~kind] = np.arange((~kind).sum())
    x = w["embed"][tokens].astype(jnp.float32) * d.embedding_multiplier
    r = d.residual_multiplier

    def layer(x, t):
        i, is_attn, j = t
        p = f32({k: w[k][i] for k in LAYER_KEYS})
        n = _rms(x, p["ln1"], d.eps)
        y = jax.lax.cond(
            is_attn,
            lambda: _attention(d, f32({k: w[k][j] for k in ATTN_KEYS}), n,
                               linear),
            lambda: _mamba(d, f32({k: w[k][j] for k in MAMBA_KEYS}), n,
                           linear))
        h = x + r * y
        n = _rms(h, p["ln2"], d.eps)
        f = jax.nn.silu(linear(n, p["w_gate"])) * linear(n, p["w_up"])
        return h + r * linear(f, p["w_down"]), None

    x, _ = jax.lax.scan(layer, x, (jnp.arange(d.layers), jnp.asarray(kind),
                                   jnp.asarray(within)))
    head = w["embed"].astype(jnp.float32).T
    return linear(_rms(x, w["ln_f"].astype(jnp.float32), d.eps),
                  head) / d.logits_scaling


@functools.partial(jax.jit, static_argnums=(0, 3))
def _gaps(d: Dims, w, tokens, control: bool, first, n):
    """As ``llama._gaps``, for this forward pass: over the positions that
    produced a served token, (sum, widest, number not 0) of the reference's
    best logit minus its logit of that token, and of the token the control
    puts first there."""
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
    """As ``llama.logit_gaps``: ``prompt + served`` padded to ``width``
    (padding lies after every position read, and both mixers are causal)."""
    seq = np.zeros(width, np.int32)
    toks = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served, np.int32)])
    if len(toks) > width:
        raise ValueError(f"request of {len(toks)} tokens > width {width}")
    seq[:len(toks)] = toks
    g, c = _gaps(d, w, jnp.asarray(seq), control, jnp.int32(len(prompt)),
                 jnp.int32(len(served)))
    return tuple(map(float, g)), tuple(map(float, c))


# -- operations and bytes ------------------------------------------------------


def _linear_params(d: Dims) -> tuple[float, float]:
    """(parameters in the layers' matmuls, parameters of the LM head)."""
    D, F, H, K, hd = d.d_model, d.d_ff, d.heads, d.kv_heads, d.head_dim
    mamba = D * (d.d_inner + d.conv_dim + d.ssm_heads) + d.d_inner * D
    attn = D * (H + 2 * K) * hd + H * hd * D
    n_m, n_a = len(d.index("mamba")), len(d.index("attention"))
    return float(n_m * mamba + n_a * attn + d.layers * 3 * D * F), float(D * d.vocab)


def _scan_flops(d: Dims) -> float:
    """FLOPs of one token's recurrence step in one Mamba layer: the decay
    and the outer product into the state (4 per element), the output's
    dot with C (2 per element), and the conv (2 per tap and channel)."""
    return (6.0 * d.ssm_heads * d.ssm_head_dim * d.d_state
            + 2.0 * d.d_conv * d.conv_dim)


def serve_flops(d: Dims, *, prefill_tokens: float, prefill_pairs: float,
                prompts: float, decode_tokens: float,
                decode_ctx: float) -> float:
    """Model FLOPs of serving: every prompt and decoded token through the
    layers' matmuls and every Mamba layer's recurrence, the LM head once
    per prompt and per decoded token, and causal attention over
    ``prefill_pairs`` + ``decode_ctx`` (query, key) pairs in each of the
    attention layers."""
    lin, head = _linear_params(d)
    n_m, n_a = len(d.index("mamba")), len(d.index("attention"))
    per_tok = 2 * lin + n_m * _scan_flops(d)
    attn = 4.0 * n_a * d.heads * d.head_dim
    return (per_tok * (prefill_tokens + decode_tokens)
            + 2 * head * (prompts + decode_tokens)
            + attn * (prefill_pairs + decode_ctx))


def ssd_step_work(d: Dims, slot_steps: float) -> tuple[float, float]:
    """(FLOP, bytes) of ``kernels/ssd_step.py`` over ``slot_steps`` (one
    sequence's token in one Mamba layer each): the state update and output
    (6 FLOP per float32 state element, 3 per x element for the skip and
    the step's scale); the state read and written once, x in and y out,
    and dt, B and C in (float32)."""
    nh, hd, ds = d.ssm_heads, d.ssm_head_dim, d.d_state
    fl = 6.0 * nh * hd * ds + 3.0 * nh * hd
    by = 4.0 * (2 * nh * hd * ds + 2 * nh * hd + nh + 2 * ds)
    return fl * slot_steps, by * slot_steps


# -- the program's layout ------------------------------------------------------


def _period(types: tuple) -> int:
    """The shortest period of the layer pattern that repeats from layer 0."""
    return next(p for p in range(1, len(types) + 1)
                if all(t == types[i % p] for i, t in enumerate(types)))


def _pattern(d: Dims) -> tuple:
    kinds = {"mamba": "ssd_mlp", "attention": "attn"}
    return tuple(kinds[t] for t in d.layer_types[:_period(d.layer_types)])


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` for a
    configuration file holding a published granitemoehybrid config.json
    with no experts."""
    d = Dims.of(cfg)
    return dict(name=cfg["name"], family="hybrid", num_layers=d.layers,
                d_model=d.d_model, num_heads=d.heads, num_kv_heads=d.kv_heads,
                d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
                block_pattern=_pattern(d), norm_eps=d.eps,
                use_rope=d.rope, tie_embeddings=cfg["tie_word_embeddings"],
                ssm_state=d.d_state, ssm_conv=d.d_conv,
                ssm_expand=cfg["mamba_expand"], ssm_head_dim=d.ssm_head_dim,
                ssm_chunk=d.chunk, ssm_conv_bias=cfg["mamba_conv_bias"],
                embedding_multiplier=d.embedding_multiplier,
                attention_multiplier=d.attention_multiplier,
                residual_multiplier=d.residual_multiplier,
                logits_scaling=d.logits_scaling,
                dtype=cfg["serve_dtype"], param_dtype=cfg["serve_dtype"])


RENAME = {"ln1": "ln1_scale", "ln2": "ln2_scale", "norm": "ssd_norm_scale",
          "out_proj": "w_out", "D": "D_skip", "A_log": "A_log",
          "dt_bias": "dt_bias", "w_gate": "w_gate", "w_up": "w_up",
          "w_down": "w_down"}


def _split(d: Dims, name: str, a):
    """One reference weight as the program's entries of one layer kind."""
    H, K, hd, di, ds = d.heads, d.kv_heads, d.head_dim, d.d_inner, d.d_state
    if name == "in_proj":
        return {"wz": a[..., :di], "wx": a[..., di:2 * di],
                "wB": a[..., 2 * di:2 * di + ds],
                "wC": a[..., 2 * di + ds:2 * di + 2 * ds],
                "wdt": a[..., 2 * di + 2 * ds:]}
    if name in ("conv_w", "conv_b"):
        sfx = "" if name == "conv_w" else "_bias"
        return {f"conv_x{sfx}": a[..., :di], f"conv_B{sfx}": a[..., di:di + ds],
                f"conv_C{sfx}": a[..., di + ds:]}
    if name in ATTN_KEYS:
        heads = K if name in ("wk", "wv") else H
        if name == "wo":
            return {"wo": a.reshape(*a.shape[:-2], H, hd, a.shape[-1])}
        return {name: a.reshape(*a.shape[:-1], heads, hd)}
    return {RENAME[name]: a}


def program_params(d: Dims, w: dict) -> dict:
    """The benchmark's weights in the program's layout: each pattern slot's
    layers stacked over the scanned periods, the layers past the last whole
    period on their own.  One jitted call per weight, which consumes it, so
    the device holds little more than one copy at a time."""
    per = _period(d.layer_types)
    n_super = d.layers // per
    slots = [(f"s{j}_", [j + per * k for k in range(n_super)])
             for j in range(per)]
    slots += [(f"t{j}_", n_super * per + j) for j in range(d.layers % per)]
    out = {}
    for name in list(w):
        if name in ("embed", "ln_f"):
            out[{"embed": "tok_embed", "ln_f": "final_scale"}[name]] = w.pop(name)
            continue
        kind = ("attention" if name in ATTN_KEYS else
                "mamba" if name in MAMBA_KEYS else None)
        rows = list(range(d.layers)) if kind is None else d.index(kind)
        take = {pre: (rows.index(ix) if isinstance(ix, int)
                      else [rows.index(i) for i in ix])
                for pre, ix in slots
                if all(i in rows for i in ([ix] if isinstance(ix, int) else ix))}

        def f(a, take=take, name=name):
            return {pre + k: v for pre, ix in take.items()
                    for k, v in _split(d, name, a[jnp.asarray(ix)]).items()}
        out.update(jax.jit(f, donate_argnums=0)(w.pop(name)))
    return out
