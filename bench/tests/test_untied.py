"""An untied head joins the benchmark by files alone: a dense
configuration whose ``lm_head`` (hidden, vocabulary) the program pads on
its second axis runs correct through the whole harness, and one token
altered where it is produced makes it not correct."""

from __future__ import annotations

import pytest

import tiny

#: deepseek-67b's dense, untied program at the tiny CPU size. The sizes
#: are stated as overrides, not by ``reduced``: the reduced config's
#: vocabulary of 512 is a multiple of the program's padding (256), and a
#: vocabulary of 500 makes the program pad ``lm_head`` to 512 columns
UNTIED = {
    "name": "tiny-untied", "reference": "dense_lm",
    "num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
    "vocab_size": 500, "qkv_bias": False, "tie_word_embeddings": False,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "program": {"arch": "deepseek-67b", "dtype": "bfloat16",
                "param_dtype": "float32", "kv_cache_dtype": "bfloat16",
                "decode_impl": "pallas",
                "overrides": {"num_layers": 2, "d_model": 128,
                              "num_heads": 4, "num_kv_heads": 2,
                              "head_dim": 32, "d_ff": 256, "vocab_size": 500,
                              "attn_impl": "direct", "kv_repeat": 1}},
}
CELL = "t.tiny-untied.tinyqa"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_tree(tmp_path_factory.mktemp("untied"))
    tiny.add_config(root, UNTIED)
    return root


def test_untied_run_is_correct(root):
    from repro.configs import get_config

    result = tiny.run(root, CELL)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    cfg = get_config("deepseek-67b")
    assert not cfg.tie_embeddings and cfg.family == "dense"
    assert cfg.padded_vocab > UNTIED["vocab_size"]


def test_token_altered_in_untied_run(root, monkeypatch):
    from repro.serving import serve

    real = serve.BatchScheduler.step

    def step(self):
        done = real(self)
        for req in done:
            if len(req.generated) > 2:
                req.generated[2] = (req.generated[2] + 1) % \
                    UNTIED["vocab_size"]
        return done

    monkeypatch.setattr(serve.BatchScheduler, "step", step)
    result = tiny.run(root, CELL)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
