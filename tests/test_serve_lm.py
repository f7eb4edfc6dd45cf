"""``launch/serve.py``'s LM path returns what it generated, and its first
decode step agrees with a no-cache forward over prompt + first token (the
check ``chip_smoke.py`` makes at full width on the chip)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import serve
from repro.models import Model

# bf16 activations: cached decode vs full forward (written down in chip_smoke)
LM_RTOL = 0.05


def test_serve_lm_generation_matches_no_cache_forward(monkeypatch, tmp_path):
    # a placed cache directory keeps main() from turning the cache on here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = serve.main(["--mode", "lm", "--arch", "qwen2-0.5b", "--smoke",
                      "--requests", "5", "--slots", "2", "--prompt-len", "8",
                      "--gen-len", "4", "--technique", "GSS"])
    prompts, tokens = out["prompts"], out["tokens"]
    logits = out["first_decode_logits"]
    assert prompts.shape == (5, 8) and tokens.shape == (5, 4)
    # the second token is the argmax of the first decode step's logits
    assert np.array_equal(tokens[:, 1], logits.argmax(axis=1))

    model = Model(get_config("qwen2-0.5b").reduced())
    params = model.init_params(jax.random.key(0))  # serve_lm's weights
    seq = jnp.asarray(np.concatenate([prompts, tokens[:, :1]], axis=1))
    positions = jnp.arange(seq.shape[1])
    x = model._embed_inputs(params, {"tokens": seq}, positions)
    x, _, _ = model._trunk(params, x, positions)
    want = np.asarray(model._logits(params, x[:, -1:])[:, 0], np.float32)
    err = np.abs(logits - want).max(axis=1) / np.abs(want).max(axis=1)
    assert err.max() <= LM_RTOL, err
