"""Serving engine: batched generation correctness and slot bookkeeping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.registry import get_model
from repro.nn import init_params
from repro.serve.engine import ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-0.6b", reduced=True).replace(
        compute_dtype="float32", remat=False)
    model = get_model(cfg)
    params = init_params(model.specs(cfg), jax.random.PRNGKey(0))
    return cfg, model, params


def test_generate_batch_matches_stepwise_argmax(setup):
    cfg, model, params = setup
    B, Tp, Tn = 2, 8, 6
    eng = ServingEngine(model, cfg, params, batch_size=B, max_len=64)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (B, Tp),
                                            0, cfg.vocab), np.int32)
    out = eng.generate_batch(prompts, Tn)
    assert out.shape == (B, Tn)

    # oracle: full forward re-scoring at every step
    seq = jnp.asarray(prompts)
    for t in range(Tn):
        logits = model.forward(params, seq, cfg)
        nxt = jnp.argmax(logits[:, -1, :].astype(jnp.float32), axis=-1)
        assert np.array_equal(np.asarray(nxt), out[:, t]), f"step {t}"
        seq = jnp.concatenate([seq, nxt[:, None].astype(jnp.int32)], axis=1)


def test_engine_slots_retire_and_refill(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, cfg, params, batch_size=2, max_len=64)
    r1 = eng.submit([3, 5, 7], max_new_tokens=4)
    r2 = eng.submit([11, 13], max_new_tokens=2)
    r3 = eng.submit([2], max_new_tokens=3)
    done = {}
    for _ in range(30):
        for fin in eng.step():
            done[fin["rid"]] = fin["tokens"]
        if len(done) == 3:
            break
    assert set(done) == {r1, r2, r3}
    assert len(done[r1]) == 4 and len(done[r2]) == 2 and len(done[r3]) == 3


def test_temperature_sampling_runs(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, cfg, params, batch_size=2, max_len=32,
                        temperature=1.0)
    prompts = np.zeros((2, 4), np.int32)
    out = eng.generate_batch(prompts, 5)
    assert out.shape == (2, 5)
    assert out.min() >= 0 and out.max() < cfg.vocab


def test_engine_from_artifact_serves_deploy_backend(setup, tmp_path):
    """Pack the LM with pack_model, save/load a DeployArtifact, and serve
    it on the deploy backend; greedy tokens must match the emulate path
    when the CIM numerics are the bottleneck-free f32 configuration."""
    import dataclasses

    from repro.api import model_artifact
    from repro.core.cim_linear import CIMConfig
    from repro.serve.engine import engine_from_artifact

    cfg, model, _ = setup
    cim = CIMConfig(enabled=True, mode="emulate", weight_bits=4, cell_bits=2,
                    act_bits=8, psum_bits=6, array_rows=32, array_cols=32)
    qcfg = dataclasses.replace(cfg, cim=cim)
    qmodel = get_model(qcfg)
    qparams = init_params(qmodel.specs(qcfg), jax.random.PRNGKey(0))

    art = model_artifact(qparams, cim, meta={"arch": "qwen3-0.6b-reduced"})
    art.save(str(tmp_path))

    eng = engine_from_artifact(str(tmp_path), qcfg, batch_size=2, max_len=32)
    assert eng.cfg.cim.mode == "deploy"
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2, 4),
                                            0, qcfg.vocab), np.int32)
    out_deploy = eng.generate_batch(prompts, 3)

    eng_e = ServingEngine(qmodel, qcfg, qparams, batch_size=2, max_len=32)
    out_emulate = eng_e.generate_batch(prompts, 3)
    assert np.array_equal(out_deploy, out_emulate)


def test_serve_launcher_deploy_runs_on_the_kernels():
    """``launch/serve.py --cim deploy`` serves a packed artifact whose
    config dispatches the Pallas kernels, not the jnp oracle."""
    from repro.launch import serve
    args = serve.parse_args(["--arch", "olmo-1b", "--reduced", "--cim",
                             "deploy", "--batch", "2", "--max-len", "16"])
    engine, cfg = serve.build_engine(args)
    assert engine.cfg.cim.mode == "deploy"
    assert engine.cfg.cim.use_kernel
    out = engine.generate_batch(np.zeros((2, 3), np.int32), 2)
    assert out.shape == (2, 2) and out.max() < cfg.vocab


def test_engine_inspection_matches_generation(setup):
    """``prefill_logits`` is the program ``generate_batch`` prefills with
    (its argmax is the first generated token), ``decode_logits`` fed that
    token gives the second, and ``lowered_step`` is the decode step it
    runs."""
    cfg, model, params = setup
    eng = ServingEngine(model, cfg, params, batch_size=2, max_len=32)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (2, 4),
                                            0, cfg.vocab), np.int32)
    logits = eng.prefill_logits(prompts)
    assert logits.shape == (2, 4, cfg.vocab)
    out = eng.generate_batch(prompts, 2)
    np.testing.assert_array_equal(logits[:, -1].argmax(-1), out[:, 0])
    step = eng.decode_logits(prompts, out[:, :1])
    assert step.shape == (2, cfg.vocab)
    np.testing.assert_array_equal(step.argmax(-1), out[:, 1])
    assert "dot_general" in eng.lowered_step().as_text()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    lives at the fixed, git-ignored ``<checkout>/.jax_cache``."""
    import os

    from repro.launch import compile_cache
    want = compile_cache.DEFAULT_CACHE_DIR
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(checkout,
                                                           ".jax_cache")
    with open(os.path.join(checkout, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
