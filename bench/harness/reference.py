"""Plain float32 reference of a dense GQA decoder, and its fp8 control: the
reference of the ``dense`` model family (``families/dense.py``).

It follows the published description of a Llama/Qwen3-style block and
imports nothing of the program: pre-norm RMSNorm, optional per-head RMSNorm
of q and k (Qwen3), rotary embedding on the half-split pairs, causal softmax
attention with grouped KV heads, SwiGLU MLP, final RMSNorm and an LM head
(the transposed embedding where the model ties them).  Every matrix product
runs at ``Precision.HIGHEST`` in float32.

Its weights are the benchmark's own (``weights.leaf``), made again from the
seed one layer at a time, so the whole model never has to sit in float32 on
the chip.  Sequences are padded at the end to one length; under causal
attention padding changes no earlier position.

The control is the same forward with every linear layer's inputs rounded to
float8 e4m3 (weights per output channel, activations per token, each scaled
to the format's range): the step below bfloat16 that would tempt a later
change.  ``gaps`` reports, per position, how far below the reference's best
logit the served token lies, and the same for the token the control puts
first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x = _q8(x, -1)          # per token
        w = _q8(w, 0)           # per output channel
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: (B, L, H, hd); rotate the half-split pairs at positions 0..L-1."""
    L, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _layer(x, w, *, c, fp8):
    B, L, d = x.shape
    nq, nkv, hd = c["heads"], c["kv_heads"], c["head_dim"]
    eps = c["eps"]
    h = _rms(x, w["norm1"], eps)
    q = _mm(h, w["wq"], fp8).reshape(B, L, nq, hd)
    k = _mm(h, w["wk"], fp8).reshape(B, L, nkv, hd)
    v = _mm(h, w["wv"], fp8).reshape(B, L, nkv, hd)
    if c["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    g = nq // nkv
    q = q.reshape(B, L, nkv, g, hd) * (hd ** -0.5)
    s = jnp.einsum("blkgh,bmkh->bkglm", q, k, precision=HI)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkglm,bmkh->blkgh", p, v, precision=HI)
    x = x + _mm(o.reshape(B, L, nq * hd), w["wo"], fp8)
    h = _rms(x, w["norm2"], eps)
    a = _mm(h, w["w_gate"], fp8)
    x = x + _mm(a * jax.nn.sigmoid(a) * _mm(h, w["w_up"], fp8),
                w["w_down"], fp8)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_gaps(hid, head, final_norm, served, *, eps, fp8):
    """hid: (B, n, d) hidden states at the positions that predicted
    ``served`` (B, n).  Returns the logits (B, n, V)'s best minus the
    served token's logit, and the control's top token."""
    logits = _mm(_rms(hid, final_norm, eps), head, fp8)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    return best - got, logits.argmax(-1).astype(jnp.int32), logits


class DenseReference:
    """Reference forward over the benchmark's seeded weights."""

    LAYER_LEAVES = ("norm1", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                    "norm2", "mlp/w_gate", "mlp/w_up", "mlp/w_down")

    def __init__(self, cfg: dict, seed: int, *, rows: int = 4):
        self.cfg = cfg
        self.seed = seed
        self.rows = rows
        d, nq, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"])
        hd = cfg.get("head_dim") or d // nq
        ff = cfg["intermediate_size"]
        self.c = dict(heads=nq, kv_heads=nkv, head_dim=hd,
                      eps=float(cfg["rms_norm_eps"]),
                      theta=float(cfg["rope_theta"]),
                      qk_norm=bool(cfg.get("qk_norm", False)))
        self.shapes = {"norm1": (d,), "norm2": (d,),
                       "attn/wq": (d, nq * hd), "attn/wk": (d, nkv * hd),
                       "attn/wv": (d, nkv * hd), "attn/wo": (nq * hd, d),
                       "attn/q_norm": (hd,), "attn/k_norm": (hd,),
                       "mlp/w_gate": (d, ff), "mlp/w_up": (d, ff),
                       "mlp/w_down": (ff, d)}
        self.leaves = self.LAYER_LEAVES + (
            ("attn/q_norm", "attn/k_norm") if self.c["qk_norm"] else ())

    def _w(self, path, shape, layer=0):
        return weights.leaf(self.seed, path, shape, self.cfg["torch_dtype"],
                            layer).astype(jnp.float32)

    def layer_weights(self, layer: int) -> dict:
        return {p.rsplit("/", 1)[-1]: self._w(f"blocks/0/{p}",
                                              self.shapes[p], layer)
                for p in self.leaves}

    def _run(self, toks, fp8s):
        """Hidden states after the last layer for (rows, L) tokens, once per
        flag in ``fp8s``; also returns the embedding for the head."""
        cfg = self.cfg
        d, V = cfg["hidden_size"], cfg["vocab_size"]
        embed = self._w("embed", (V, d))
        x0 = embed[jnp.asarray(toks)]
        xs = {f: x0 for f in fp8s}
        for layer in range(cfg["num_hidden_layers"]):
            w = self.layer_weights(layer)
            for f in xs:
                xs[f] = _layer(xs[f], w, c=_frozen(self.c), fp8=f)
        head = (embed.T if cfg.get("tie_word_embeddings")
                else self._w("lm_head", (d, V)))
        return xs, head, self._w("final_norm", (d,))

    def logits(self, seq, positions):
        """float32 logits (n, V) at ``positions`` of one sequence."""
        L = -(-len(seq) // 128) * 128
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(seq)] = seq
        xs, head, fnorm = self._run(toks, (False,))
        hid = xs[False][:, jnp.asarray(positions)]
        *_, logits = _head_gaps(hid, head, fnorm,
                                jnp.zeros(hid.shape[:2], jnp.int32),
                                eps=self.c["eps"], fp8=False)
        return np.asarray(logits[0])

    def gaps(self, seqs, served, *, control: bool = False):
        """``seqs[i]``: prompt followed by the served tokens but the last;
        ``served[i]``: the served tokens (the logits at positions
        ``len(prompt) - 1 ..`` predict them).  Returns per request the gap
        of each served token below the reference's best logit and, with
        ``control``, the gap of the token the fp8 control puts first."""
        L = max(len(s) for s in seqs)
        L = -(-L // 128) * 128
        n = max(len(t) for t in served)
        out = [None] * len(seqs)
        ctrl = [None] * len(seqs)
        for lo in range(0, len(seqs), self.rows):
            idx = list(range(lo, min(lo + self.rows, len(seqs))))
            toks = np.zeros((self.rows, L), np.int32)
            want = np.zeros((self.rows, n), np.int32)
            tok_served = np.zeros((self.rows, n), np.int32)
            for r, i in enumerate(idx):
                s, t = np.asarray(seqs[i]), np.asarray(served[i])
                toks[r, :len(s)] = s
                p0 = len(s) - len(t)            # position predicting t[0]
                want[r, :len(t)] = p0 + np.arange(len(t))
                tok_served[r, :len(t)] = t
            xs, head, fnorm = self._run(toks, (False, True) if control
                                        else (False,))
            wj = jnp.asarray(want)[..., None]
            hid = {f: jnp.take_along_axis(x, wj, 1) for f, x in xs.items()}
            gap, _, ref_logits = _head_gaps(
                hid[False], head, fnorm, jnp.asarray(tok_served),
                eps=self.c["eps"], fp8=False)
            gap = np.asarray(gap)
            if control:
                _, top8, _ = _head_gaps(hid[True], head, fnorm,
                                        jnp.asarray(tok_served),
                                        eps=self.c["eps"], fp8=True)
                best = ref_logits.max(-1)
                got = jnp.take_along_axis(ref_logits, top8[..., None],
                                          -1)[..., 0]
                cgap = np.asarray(best - got)
            for r, i in enumerate(idx):
                m = len(served[i])
                out[i] = gap[r, :m]
                if control:
                    ctrl[i] = cgap[r, :m]
            del xs, hid, ref_logits
        return (out, ctrl) if control else out


class _frozen(dict):
    """A hashable dict for jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
