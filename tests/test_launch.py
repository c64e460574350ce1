"""Entry-point plumbing: the persistent compile cache and the serve entry point's
failure accounting."""
import jax
import pytest

from repro.launch import compile_cache
from repro.launch.serve import check_outcome
from repro.serve.api import Response


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config updates instead of applying them (the cache must
    not be switched on for the rest of the test process)."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert config_updates["jax_compilation_cache_dir"] == path
    assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_compile_cache_follows_the_environment(monkeypatch, config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_counts_hits_and_misses():
    before = compile_cache.stats()
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_event("/jax/some/other/event")
    after = compile_cache.stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"] + 1


def _ok(uid):
    return Response(uid=uid, kind="predict")


def test_serve_outcome_passes_clean_runs(capsys):
    check_outcome("cnn", [_ok("a"), _ok("b")], 0, admission=None)
    assert "0 errors, 0 shed" in capsys.readouterr().out


def test_serve_outcome_fails_on_any_error():
    bad = Response(uid="b", kind="explain", error="boom",
                   error_type="RuntimeError")
    with pytest.raises(SystemExit, match="1 requests failed"):
        check_outcome("cnn", [_ok("a"), bad], 0, admission=object())


@pytest.mark.parametrize("at_submit", [0, 1])
def test_serve_outcome_sheds_fail_only_without_admission(at_submit):
    shed = [] if at_submit else [Response(uid="s", kind="explain",
                                          error="expired",
                                          error_type="ShedError")]
    check_outcome("cnn", [_ok("a")] + shed, at_submit, admission=object())
    with pytest.raises(SystemExit, match="no admission control"):
        check_outcome("cnn", [_ok("a")] + shed, at_submit, admission=None)
