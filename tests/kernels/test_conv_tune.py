"""Backend resolution + autotune cache (kernels/conv2d/tune.py)."""
import jax
import pytest

from repro.kernels.conv2d import tune


def test_resolve_interpret_auto(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    expect = jax.default_backend() != "tpu"
    assert tune.resolve_interpret(None) is expect
    assert tune.resolve_interpret(True) is True
    assert tune.resolve_interpret(False) is False


def test_resolve_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert tune.resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert tune.resolve_interpret(None) is True
    # explicit argument beats the env var
    assert tune.resolve_interpret(False) is False


def test_autotune_measures_once_and_caches():
    tune.clear_cache()
    calls = []

    def measure(cand):
        calls.append(cand)
        return {(8, 8): 3.0, (16, 16): 1.0, (32, 32): 2.0}[cand]

    key = ("test", 1, 2, 3)
    cands = [(8, 8), (16, 16), (32, 32)]
    assert tune.autotune(key, cands, measure) == (16, 16)
    assert len(calls) == 3
    # cache hit: no re-measurement
    assert tune.autotune(key, cands, measure) == (16, 16)
    assert len(calls) == 3
    info = tune.cache_info()
    assert info["entries"] == 1 and info["hits"] == 1
    tune.clear_cache()


def test_autotune_skips_failing_candidates():
    tune.clear_cache()

    def measure(cand):
        if cand == (8,):
            raise RuntimeError("compiler rejected blocking")
        return 1.0

    assert tune.autotune(("k",), [(8,), (16,)], measure) == (16,)
    # the refusal is counted and its error kept, not swallowed
    info = tune.cache_info()
    assert info["refused"] == 1
    assert info["errors"] == [(("k",), (8,),
                               "RuntimeError: compiler rejected blocking")]
    tune.clear_cache()
    assert tune.cache_info()["refused"] == 0
    assert tune.cache_info()["errors"] == []


def test_autotune_raises_when_every_candidate_refused():
    """No runnable blocking: the tune raises with the compiler's message
    and caches nothing (it used to cache candidates[0] silently)."""
    tune.clear_cache()

    def measure(cand):
        raise ValueError(f"block {cand} exceeds VMEM")

    with pytest.raises(RuntimeError, match=r"block \(16,\) exceeds VMEM"):
        tune.autotune(("k",), [(8,), (16,)], measure)
    info = tune.cache_info()
    assert info["entries"] == 0 and info["refused"] == 2
    tune.clear_cache()


def test_blocks_clip_to_problem_shape():
    """Interpret mode: deterministic heuristic blocks, pow2-clipped so tiny
    problems do not pad up to 128**3."""
    tune.clear_cache()
    assert tune.matmul_blocks(100, 70, 50, "float32",
                              interpret=True) == (128, 128, 64)
    assert tune.matmul_blocks(5, 3, 2, "float32",
                              interpret=True) == (8, 8, 8)
    bm, bn = tune.conv_blocks(2, 5, 5, 3, 7, 11, 2, "float32",
                              interpret=True)
    assert bm == 32 and bn == 16      # pow2ceil(25), pow2ceil(11)
    # second call is a pure cache hit
    before = tune.cache_info()["hits"]
    tune.matmul_blocks(100, 70, 50, "float32", interpret=True)
    assert tune.cache_info()["hits"] == before + 1
    tune.clear_cache()


def test_time_call_runs_on_device_under_trace():
    """Tuners measure at trace time: time_call must execute its calls
    before it returns, not stage them into the program being traced."""
    import numpy as np
    from repro.kernels import common
    ran = []

    def probe(x):
        jax.debug.callback(lambda: ran.append(1))
        return x * 2

    def traced(y):
        common.time_call(probe, np.ones(3, np.float32), iters=2)
        assert len(ran) == 3              # warm-up + 2 timed calls
        return y

    jax.jit(traced)(1.0)


@pytest.mark.parametrize("env,measured", [("0", 0), ("1", 1)])
def test_autotune_env_gate(monkeypatch, env, measured):
    """REPRO_CONV_AUTOTUNE=0 disables measurement even off-interpret; with
    it on, a compiled-backend tune would measure (exercised via the
    public entry point on tiny shapes in interpret=False... too slow on
    CPU, so assert through the gate instead)."""
    tune.clear_cache()
    monkeypatch.setenv("REPRO_CONV_AUTOTUNE", env)
    assert tune._autotune_enabled(interpret=False) is bool(measured)
    assert tune._autotune_enabled(interpret=True) is False
    tune.clear_cache()
