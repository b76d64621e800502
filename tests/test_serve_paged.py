"""The paged path of `serve.generate`: one jitted step for every call, the
cache donated to it, for a dense and a latent-attention MoE model."""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.launch import serve
from repro.models import model as M


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "moonlight-16b-a3b"])
def test_generate_reuses_one_donating_step(arch, monkeypatch):
    cfg = get_reduced_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0), tp=1)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab_size)
    pcfg = serve.build_paged_config(8, 4)
    first = serve.generate(cfg, params, prompt, 10, pcfg)

    step, fed = serve._paged_step, []

    def spy(*args, **kw):
        fed.append(args[4])
        return step(*args, **kw)

    monkeypatch.setattr(serve, "_paged_step", spy)
    compiled = step._cache_size()
    again = serve.generate(cfg, params, prompt, 10, pcfg)
    assert step._cache_size() == compiled  # the second call traced nothing
    assert len(fed) == 6 + 10 - 1
    # every cache a step was given was donated to it (its buffers are gone)
    assert all(leaf.is_deleted() for kv in fed for leaf in jax.tree.leaves(kv))
    np.testing.assert_array_equal(np.asarray(first.tokens), np.asarray(again.tokens))
    np.testing.assert_array_equal(np.asarray(first.logits), np.asarray(again.logits))
    assert first.promoted == again.promoted
    assert first.local_expert_slots == again.local_expert_slots
