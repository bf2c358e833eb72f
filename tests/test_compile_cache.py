"""Where the persistent compilation cache goes.  Only the directory is
resolved here; the tests never turn the cache on."""
import pathlib

from repro.launch import compile_cache as CC

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_places_the_cache(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    assert CC.compile_cache_dir() == str(tmp_path)


def test_default_is_the_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    assert CC.compile_cache_dir() == str(REPO / ".jax_cache")


def test_empty_env_var_falls_back_to_the_default(monkeypatch):
    monkeypatch.setenv(CC.ENV_VAR, "")
    assert CC.compile_cache_dir() == str(REPO / ".jax_cache")


def test_default_cache_dir_is_gitignored():
    rel = CC.DEFAULT_DIR.relative_to(REPO)
    ignore = (REPO / ".gitignore").read_text().split()
    assert f"{rel}/" in ignore or str(rel) in ignore
