"""The dense decoder-only LM family: weights, the plain reference, counts.

A configuration file of this family (``"model": "dense"``) holds the
published ``config.json`` keys of a dense decoder-only LM plus what the
file does not say (``assumed``).  This module

* turns it into the program's ``ModelConfig`` (the system under test);
* makes the weights from ``--seed`` on the device, in one jitted call, in
  the dtype they are served in, laid out as the program's parameter tree;
* computes the plain float32 reference forward pass over the same weights
  (``jax.numpy`` only, no kernel, cache or batching), and the lower
  precision control of that reference.

The reference imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    norm: str            # "nonparametric" (LayerNorm, no affine) | "rms"
    norm_eps: float
    tied: bool
    dtype: str

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads


def dims(config: Dict[str, Any]) -> Dims:
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    a = config.get("assumed", {})
    return Dims(
        layers=int(config["num_hidden_layers"]),
        d=d,
        heads=heads,
        kv_heads=int(config.get("num_key_value_heads", heads)),
        head_dim=int(config.get("head_dim", d // heads)),
        ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        rope_theta=float(config.get("rope_theta", 10000.0)),
        norm=str(a.get("norm", "rms")),
        norm_eps=float(config.get("rms_norm_eps", a.get("norm_eps", 1e-6))),
        tied=bool(config.get("tie_word_embeddings", False)),
        dtype=str(config.get("serve_dtype", "bfloat16")),
    )


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for this file: the registry entry with
    the file's sizes.  Refuses a file the program cannot run as stated."""
    from repro.configs import dense_stages, get_config

    base = get_config(config["registry"])
    m = dims(config)
    if config.get("attention_bias"):
        raise ValueError(f"{config['name']}: the program has no attention bias")
    mrope = (config.get("rope_scaling") or {}).get("mrope_section")
    cfg = dataclasses.replace(
        base,
        stages=dense_stages(m.layers),
        d_model=m.d,
        n_heads=m.heads,
        n_kv_heads=m.kv_heads,
        head_dim=m.head_dim,
        d_ff=m.ff,
        vocab_size=m.vocab,
        rope_theta=m.rope_theta,
        mrope_sections=tuple(mrope) if mrope else None,
        norm=m.norm,
        activation="silu",
        tie_embeddings=m.tied,
        qk_norm=False,
        window=None,
        dtype=m.dtype,
        param_dtype=m.dtype,
    )
    cfg.validate()
    return cfg


# ------------------------------------------------------------------ weights
def _layer_shapes(m: Dims) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Per-layer weight shapes (program layout) and their init scale."""
    return {
        "wq": ((m.d, m.kv_heads, m.group, m.head_dim), 1 / math.sqrt(m.d)),
        "wk": ((m.d, m.kv_heads, m.head_dim), 1 / math.sqrt(m.d)),
        "wv": ((m.d, m.kv_heads, m.head_dim), 1 / math.sqrt(m.d)),
        "wo": ((m.kv_heads, m.group, m.head_dim, m.d), 1 / math.sqrt(m.heads * m.head_dim)),
        "w_gate": ((m.d, m.ff), 1 / math.sqrt(m.d)),
        "w_up": ((m.d, m.ff), 1 / math.sqrt(m.d)),
        "w_down": ((m.ff, m.d), 1 / math.sqrt(m.ff)),
    }


def make_weights(m: Dims, seed: int) -> Dict[str, Any]:
    """Random normal weights from ``seed``, made on the device in one jitted
    call in the served dtype, in the program's parameter tree:
    ``embed``, ``final_norm`` and ``stage0`` with ``l0_s0_attn`` /
    ``l0_s1_mlp`` leaves stacked over the layers."""
    dtype = jnp.dtype(m.dtype)
    shapes = _layer_shapes(m)

    def norm_params():
        return {} if m.norm == "nonparametric" else {"scale": jnp.ones((m.layers, m.d), dtype)}

    def build(key):
        keys = jax.random.split(key, len(shapes) + 2)

        def leaf(k, shape, scale):
            return (jax.random.normal(k, (m.layers,) + shape, jnp.float32) * scale).astype(dtype)

        w = {n: leaf(k, s, sc) for k, (n, (s, sc)) in zip(keys[2:], shapes.items())}
        tree = {
            "embed": (
                jax.random.normal(keys[0], (m.vocab, m.d), jnp.float32) / math.sqrt(m.d)
            ).astype(dtype),
            "final_norm": {} if m.norm == "nonparametric" else {"scale": jnp.ones((m.d,), dtype)},
            "stage0": {
                "l0_s0_attn": {
                    "norm": norm_params(),
                    "body": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                },
                "l0_s1_mlp": {
                    "norm": norm_params(),
                    "body": {n: w[n] for n in ("w_gate", "w_up", "w_down")},
                },
            },
        }
        if not m.tied:
            tree["lm_head"] = (
                jax.random.normal(keys[1], (m.vocab, m.d), jnp.float32) / math.sqrt(m.d)
            ).astype(dtype)
        return tree

    # seeds above 32 bits are folded, not truncated
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    return jax.block_until_ready(jax.jit(build)(key))


def matmul_params(m: Dims) -> int:
    """Parameters that take part in a matrix multiplication per token,
    the (tied) output head included."""
    per_layer = sum(int(np.prod(s)) for s, _ in _layer_shapes(m).values())
    return m.layers * per_layer + m.vocab * m.d


def decode_flops(m: Dims, context: int) -> float:
    """Model FLOPs of one decoded token at ``context`` live positions:
    2 per matmul parameter, plus QK^T and PV over the context."""
    return 2.0 * matmul_params(m) + 4.0 * m.layers * m.heads * m.head_dim * context


def attention_layers(m: Dims) -> int:
    """Layers that run paged attention: every layer."""
    return m.layers


# ---------------------------------------------------------------- reference
def _norm(m: Dims, x):
    if m.norm == "nonparametric":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m.norm_eps)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + m.norm_eps)


def _rope(x, pos, theta):
    """Rotary embedding, rotate-half convention; x (S, H, Hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1
    )


def _fp8(w):
    """Round a weight to float8 e4m3 with one scale per output column."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUERY_BLOCK = 512        # prompt queries attended per block: bounds the score matrix


def _layer_weights(weights):
    att = weights["stage0"]["l0_s0_attn"]["body"]
    mlp = weights["stage0"]["l0_s1_mlp"]["body"]
    return (att["wq"], att["wk"], att["wv"], att["wo"], mlp["w_gate"], mlp["w_up"], mlp["w_down"])


def _reference(m: Dims, control: bool):
    """The plain forward pass, split at the prompt so that one prompt's
    keys and values serve every sequence that continues it:

    * ``prompt(weights, tokens (P,)) -> kv (L, 2, P, KVH, Hd)``: each
      layer's rotated keys and values over the prompt, its queries
      attended in blocks of ``QUERY_BLOCK`` so that long prompts fit;
    * ``suffix(weights, kv, tokens (S,)) -> logits (S, V)``: the tokens at
      positions ``P .. P+S-1``, attending to the prompt and causally to
      each other.

    float32 at the highest matmul precision; ``control`` rounds every
    matrix to float8 with one scale per output channel first: the lower
    precision a later change might be tempted by."""

    def cast(w, inputs_first=True):
        if not control:
            return w.astype(jnp.float32)
        flat = w.reshape(w.shape[0], -1) if inputs_first else w.reshape(-1, w.shape[-1])
        return _fp8(flat).reshape(w.shape)

    def qkv(w, x, pos):
        S = x.shape[0]
        h = _norm(m, x)
        q = jnp.einsum("sd,dkgh->skgh", h, cast(w[0])).reshape(S, m.heads, m.head_dim)
        k = jnp.einsum("sd,dkh->skh", h, cast(w[1]))
        v = jnp.einsum("sd,dkh->skh", h, cast(w[2]))
        return _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta), v

    def attend(q, k, v, visible):
        """q (S, H, Hd) over k, v (T, KVH, Hd); visible (S, T)."""
        kh = jnp.repeat(k, m.group, axis=1)               # head j reads kv head j // G
        vh = jnp.repeat(v, m.group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, kh) / math.sqrt(m.head_dim)
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vh)

    def finish(w, x, ctx):
        S = x.shape[0]
        ctx = ctx.reshape(S, m.kv_heads, m.group, m.head_dim)
        x = x + jnp.einsum("skgh,kghd->sd", ctx, cast(w[3], inputs_first=False))
        h = _norm(m, x)
        act = jax.nn.silu(h @ cast(w[4])) * (h @ cast(w[5]))
        return x + act @ cast(w[6])

    def prompt(weights, tokens):
        with jax.default_matmul_precision("highest"):
            P = tokens.shape[0]
            nb = -(-P // QUERY_BLOCK)
            pos = jnp.arange(P)
            x = weights["embed"][tokens].astype(jnp.float32)

            def layer(x, w):
                q, k, v = qkv(w, x, pos)
                qb = jnp.pad(q, ((0, nb * QUERY_BLOCK - P), (0, 0), (0, 0)))
                qb = qb.reshape(nb, QUERY_BLOCK, m.heads, m.head_dim)

                def block(args):
                    i, qi = args
                    qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
                    return attend(qi, k, v, qpos[:, None] >= pos[None, :])

                ctx = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(nb * QUERY_BLOCK, m.heads, m.head_dim)
                return finish(w, x, ctx[:P]), jnp.stack([k, v])

            _, kv = jax.lax.scan(layer, x, _layer_weights(weights))
            return kv

    def suffix(weights, kv, tokens):
        with jax.default_matmul_precision("highest"):
            P, S = kv.shape[2], tokens.shape[0]
            pos = P + jnp.arange(S)
            visible = jnp.concatenate(
                [jnp.ones((S, P), bool), pos[:, None] >= pos[None, :]], axis=1)
            x = weights["embed"][tokens].astype(jnp.float32)

            def layer(x, wkv):
                w, pkv = wkv
                q, k, v = qkv(w, x, pos)
                ctx = attend(q, jnp.concatenate([pkv[0], k]), jnp.concatenate([pkv[1], v]), visible)
                return finish(w, x, ctx), None

            x, _ = jax.lax.scan(layer, x, (_layer_weights(weights), kv))
            head = weights["embed"] if m.tied else weights["lm_head"]
            return _norm(m, x) @ cast(head.T)

    return prompt, suffix


class Reference:
    """The widest gaps of served greedy tokens against the float32
    reference, one sequence at a time, each prompt's pass run once.

    ``gaps(weights, prompt, rest, check)`` takes a served sequence split
    after its prompt's first ``P - 1`` tokens (``rest`` starts with the
    prompt's last token, whose logits choose the first served token) and
    returns, for each ``t`` with ``check[t]``, how far below the
    reference's best logit at ``rest[t - 1]`` the token ``rest[t]`` lies.
    With ``control`` the served token is replaced by the first choice of
    the float8 control, computed over the same prompt and tokens."""

    def __init__(self, m: Dims, *, control: bool = False):
        self.prompt, suffix = (jax.jit(f) for f in _reference(m, control=False))
        self.suffix = suffix
        self.low = tuple(jax.jit(f) for f in _reference(m, control=True)) if control else None
        self._kv = {}

        def gaps(logits, served, check):
            best = jnp.max(logits[:-1], axis=-1)
            got = jnp.take_along_axis(logits[:-1], served[:, None], axis=-1)[:, 0]
            return jnp.where(check[1:], best - got, 0.0)

        self._gaps = jax.jit(gaps)

    def _prompt_kv(self, weights, prompt, low: bool):
        key = (low, prompt.tobytes())
        if key not in self._kv:
            fn = self.low[0] if low else self.prompt
            self._kv[key] = fn(weights, jnp.asarray(prompt))
        return self._kv[key]

    def gaps(self, weights, prompt: np.ndarray, rest: np.ndarray, check: np.ndarray) -> np.ndarray:
        toks = jnp.asarray(rest)
        logits = self.suffix(weights, self._prompt_kv(weights, prompt, False), toks)
        if self.low is not None:
            low = self.low[1](weights, self._prompt_kv(weights, prompt, True), toks)
            served = jnp.argmax(low[:-1], axis=-1)
        else:
            served = toks[1:]
        return np.asarray(jax.device_get(self._gaps(logits, served, jnp.asarray(check))))
