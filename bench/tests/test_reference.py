"""The benchmark's own encoder and reference decoder agree with the
textbook and with the program at a small size on the CPU."""

import numpy as np
import pytest

from codes import Code, encode, make_codewords, viterbi_blocks

CODES = {"ccsds": Code(7, (0o171, 0o133)), "is95-k9": Code(9, (0o753, 0o561))}


def _frame(y: np.ndarray, D: int, L: int) -> np.ndarray:
    n = len(y)
    nb = -(-n // D)
    pad = np.zeros((nb * D + 3 * L, y.shape[1]), np.int32)
    pad[L : L + n] = y
    return np.stack([pad[b * D : b * D + D + 2 * L] for b in range(nb)])


def test_encoder_by_hand():
    # K=3, g = 7, 5 octal: input 1 0 1 1 -> 11 10 00 01 (textbook example)
    out = encode(np.array([1, 0, 1, 1]), Code(3, (0o7, 0o5)))
    assert out.tolist() == [[1, 1], [1, 0], [0, 0], [0, 1]]


@pytest.mark.parametrize("name", sorted(CODES))
def test_noiseless_codewords_decode_to_their_payload(name):
    code = CODES[name]
    rng = np.random.default_rng(1)
    y = make_codewords(rng, 2, 200, code, ebn0_db=100.0, q=8).reshape(-1, code.R)
    # the same draw again gives the payload that make_codewords encoded
    payload = np.random.default_rng(1).integers(0, 2, (2, 200 - code.v), dtype=np.uint8)
    bits = viterbi_blocks(_frame(y, 64, 14), code, L=14, D=64, batch=4).reshape(-1)[: len(y)]
    expect = np.concatenate([np.concatenate([p, np.zeros(code.v, np.uint8)]) for p in payload])
    assert np.array_equal(bits, expect)


@pytest.mark.parametrize("name", sorted(CODES))
def test_noisy_blocks_match_the_program(name):
    import jax.numpy as jnp

    from repro.core.codespec import get_code_spec
    from repro.core.engine import DecoderEngine
    from repro.core.pbvd import PBVDConfig

    code = CODES[name]
    D, L = 64, 14
    y = make_codewords(np.random.default_rng(7), 3, 256, code, ebn0_db=2.0, q=8).reshape(-1, 2)
    eng = DecoderEngine(PBVDConfig(spec=get_code_spec(name), D=D, L=L, q=8, backend="ref"))
    got = np.asarray(eng.decode(jnp.asarray(y), len(y)))
    ref = viterbi_blocks(_frame(y, D, L), code, L=L, D=D, batch=8).reshape(-1)[: len(y)]
    assert np.array_equal(ref, got)
