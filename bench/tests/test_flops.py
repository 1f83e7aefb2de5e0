"""FLOP and byte counts against hand-worked qwen2-0.5b numbers
(hidden 896, 14 heads of 64, 2 KV heads, MLP 4864, 24 layers, vocabulary
151936, tied embeddings)."""

from __future__ import annotations

import json

import tiny
from benchlib import flops

M = json.loads((tiny.BENCH / "configs" / "qwen2-0.5b.json").read_text())


def test_parameters():
    # per layer: q 896*14*64 = 802816, k and v 2*896*2*64 = 229376,
    # o 802816, MLP 3*896*4864 = 13074432 -> 14909440; 24 layers
    assert flops.matmul_params(M) == 357_826_560
    # plus embedding 151936*896 = 136134656, norms 49*896 = 43904 and
    # biases 24*(14+2+2)*64 = 27648: the published 494M
    total = 357_826_560 + 136_134_656 + 43_904 + 27_648
    assert total == 494_032_768


def test_decode_and_prefill():
    assert flops.head_flops(M) == 2 * 896 * 151936 == 272_269_312
    # one token at context 100: 2*357826560 + 4*24*14*64*100 + head
    assert flops.decode_flops(M, 100) == \
        715_653_120 + 8_601_600 + 272_269_312
    # a 2-token prompt: both tokens through the stack, contexts 1 and 2,
    # logits at the last position
    assert flops.prefill_flops(M, 2) == \
        2 * 715_653_120 + 4 * 24 * 14 * 64 * (1 + 2) + 272_269_312
    # prefill of 3 gives the first token; 2 decode steps at contexts 4, 5
    assert flops.generate_flops(M, 3, 3) == flops.prefill_flops(M, 3) + \
        flops.decode_flops(M, 4) + flops.decode_flops(M, 5)


def test_decode_attention_work():
    ops, nbytes = flops.decode_attention_work(M, 100)
    assert ops == 4 * 14 * 64 * 100 == 358_400
    # K and V: 100 positions * 2 heads * 64 * 2 bytes each; q and out
    assert nbytes == 2 * 100 * 2 * 64 * 2 + 2 * 14 * 64 * 2 == 54_784
