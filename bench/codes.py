"""The yardstick's own convolutional code, channel and reference decoder.

Nothing here imports the program under test: the symbols a run sends and
the bits it expects back are made and checked by this file alone, so a
change to the program cannot move them.

Conventions (textbook, e.g. Lin & Costello ch. 12):

* A rate-1/R code of constraint length K has R generator polynomials,
  written in octal with the most significant of the K bits tapping the
  current input bit.
* The encoder register holds the current input in bit K-1 and the v = K-1
  previous inputs below it, the newest in bit v-1. Output r at a stage is
  the parity of ``register & g_r``.
* A state is the register's lower v bits: the last v inputs, newest in the
  most significant bit. Input x moves state s to ``(x << (v-1)) | (s >> 1)``.
* BPSK maps bit 0 to +1 and bit 1 to -1; a q-bit soft symbol is
  ``round(y * (2**(q-1) - 1) / 4)`` clipped to +-(2**(q-1) - 1).
* The branch cost of output bits c on symbols y is ``sum_r y_r (2 c_r - 1)``
  and the decoder keeps, for each state, the predecessor of least cost; on
  a tie it keeps the predecessor whose dropped (oldest) bit is 0.
* A block decode (parallel-block Viterbi, arXiv:1608.00066 sec. III) of
  block b runs over the received stages [bD - L, bD + D + L), with zeros
  outside the stream, from all path metrics equal, and traces back from
  state 0 at the window's end; it emits the bits of stages [bD, bD + D).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

__all__ = ["Code", "code_from_config", "encode", "make_codewords", "viterbi_blocks"]


@dataclasses.dataclass(frozen=True)
class Code:
    """A rate-1/R feed-forward convolutional code."""

    K: int
    polys: tuple[int, ...]  # generator polynomials as integers (octal in configs)

    @property
    def R(self) -> int:
        return len(self.polys)

    @property
    def v(self) -> int:
        return self.K - 1

    @property
    def n_states(self) -> int:
        return 1 << self.v


def code_from_config(cfg: dict) -> Code:
    """The code a configuration file states (``code.K``, ``code.polys_octal``)."""
    c = cfg["code"]
    return Code(K=int(c["K"]), polys=tuple(int(str(p), 8) for p in c["polys_octal"]))


def encode(bits: np.ndarray, code: Code) -> np.ndarray:
    """(..., n) input bits from state 0 -> (..., n, R) coded bits."""
    bits = np.asarray(bits, np.uint8)
    n = bits.shape[-1]
    out = np.zeros(bits.shape + (code.R,), np.uint8)
    for r, g in enumerate(code.polys):
        for i in range(code.K):  # tap i of g reads the input i stages ago
            if (g >> (code.K - 1 - i)) & 1:
                if i == 0:
                    out[..., r] ^= bits
                else:
                    out[..., i:, r] ^= bits[..., : n - i]
    return out


def make_codewords(
    rng: np.random.Generator, n: int, stages: int, code: Code, ebn0_db: float, q: int
) -> np.ndarray:
    """``n`` terminated codewords of ``stages`` stages each (``stages - v``
    random payload bits, then v zeros), sent as BPSK over AWGN at
    ``ebn0_db`` and quantized to ``q``-bit soft symbols: (n, stages, R) int8."""
    payload = rng.integers(0, 2, (n, stages - code.v), dtype=np.uint8)
    bits = np.concatenate([payload, np.zeros((n, code.v), np.uint8)], axis=1)
    coded = encode(bits, code)
    esn0 = 10.0 ** (ebn0_db / 10.0) / code.R
    sigma = math.sqrt(1.0 / (2.0 * esn0))
    y = 1.0 - 2.0 * coded.astype(np.float32)
    y += sigma * rng.standard_normal(y.shape, dtype=np.float32)
    qmax = (1 << (q - 1)) - 1
    return np.clip(np.round(y * (qmax / 4.0)), -qmax, qmax).astype(np.int8)


def _trellis(code: Code):
    """For every state s and each of its two predecessors j (dropped bit j):
    the predecessor's index and the label (R output bits as an integer,
    output 0 in the most significant bit) of the branch into s."""
    N, v = code.n_states, code.v
    s = np.arange(N)
    pred = np.stack([((s << 1) & (N - 1)) | j for j in (0, 1)], axis=1)  # (N, 2)
    x = s >> (v - 1)
    label = np.zeros((N, 2), np.int64)
    for r, g in enumerate(code.polys):
        reg = (x[:, None] << v) | pred
        bit = np.array([bin(int(t)).count("1") & 1 for t in (reg & g).ravel()]).reshape(N, 2)
        label |= bit << (code.R - 1 - r)
    return pred, label


def viterbi_blocks(windows: np.ndarray, code: Code, L: int, D: int, batch: int = 1024):
    """Decode framed blocks: (B, T, R) integer soft symbols, T = D + 2L ->
    (B, D) bits, by the convention in the module docstring.

    Runs on JAX's default device in batches of ``batch`` blocks (one
    compiled shape), with exact int32 path metrics.
    """
    import jax
    import jax.numpy as jnp

    B, T, R = windows.shape
    if T != D + 2 * L or R != code.R:
        raise ValueError(f"windows {windows.shape} do not match T={D + 2 * L}, R={code.R}")
    N, v = code.n_states, code.v
    pred, label = _trellis(code)
    # signs[c, r] = 2 c_r - 1 for every label c
    c = np.arange(1 << R)
    signs = np.stack([2 * ((c >> (R - 1 - r)) & 1) - 1 for r in range(R)], axis=1)
    pred_j, label_j, signs_j = jnp.asarray(pred), jnp.asarray(label), jnp.asarray(signs)

    @jax.jit
    def decode(y):  # (b, T, R) int32 -> (b, D) int32
        b = y.shape[0]

        def forward(pm, y_t):  # pm (b, N); y_t (b, R)
            cost = y_t @ signs_j.T  # (b, 2^R)
            cand = pm[:, pred_j] + cost[:, label_j]  # (b, N, 2)
            take1 = cand[:, :, 1] < cand[:, :, 0]
            return jnp.where(take1, cand[:, :, 1], cand[:, :, 0]), take1

        _, dec = jax.lax.scan(forward, jnp.zeros((b, N), jnp.int32), jnp.swapaxes(y, 0, 1))

        def back(state, dec_t):  # walk stage t from the state after it
            bit = state >> (v - 1)
            drop = jnp.take_along_axis(dec_t, state[:, None], axis=1)[:, 0]
            return ((state << 1) & (N - 1)) | drop.astype(jnp.int32), bit

        _, bits = jax.lax.scan(back, jnp.zeros((b,), jnp.int32), dec, reverse=True)
        return jnp.swapaxes(bits[L : L + D], 0, 1)

    out = np.zeros((B, D), np.int32)
    for lo in range(0, B, batch):
        part = windows[lo : lo + batch].astype(np.int32)
        n = len(part)
        if n < batch:
            part = np.concatenate([part, np.zeros((batch - n, T, R), np.int32)])
        out[lo : lo + n] = np.asarray(decode(jnp.asarray(part)))[:n]
    return out
