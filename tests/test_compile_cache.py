"""Placement of XLA's persistent compilation cache
(:mod:`lddl_tpu.core.compile_cache`): the directory is part of the cache
key, so it is either where the environment puts it or one fixed path in
the checkout — never the working directory, a temp dir or a pid."""

import os
import subprocess
import sys

import jax

from lddl_tpu.core import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_placement_leaves_jax_config_alone(monkeypatch, tmp_path):
  monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
  monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
  before = jax.config.jax_compilation_cache_dir
  assert compile_cache.use_compile_cache() == str(tmp_path)
  assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_in_the_checkout(tmp_path):
  """Same absolute path whatever the working directory (two fresh
  interpreters, two cwds), inside the checkout and git-ignored."""
  code = ('from lddl_tpu.core.compile_cache import DEFAULT_CACHE_DIR; '
          'print(DEFAULT_CACHE_DIR)')
  env = dict(os.environ, PYTHONPATH=REPO_ROOT)
  seen = {
      subprocess.run([sys.executable, '-c', code], cwd=cwd, env=env,
                     check=True, capture_output=True,
                     text=True).stdout.strip()
      for cwd in (REPO_ROOT, str(tmp_path))
  }
  assert seen == {os.path.join(REPO_ROOT, '.jax_cache')}
  with open(os.path.join(REPO_ROOT, '.gitignore')) as f:
    assert '.jax_cache/' in f.read().split()


def test_default_applies_to_accelerators_only(monkeypatch):
  monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
  before = jax.config.jax_compilation_cache_dir
  assert jax.default_backend() == 'cpu'
  assert compile_cache.use_compile_cache() is None
  assert jax.config.jax_compilation_cache_dir == before
  monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
  try:
    assert compile_cache.use_compile_cache() == \
        compile_cache.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == \
        compile_cache.DEFAULT_CACHE_DIR
  finally:
    jax.config.update('jax_compilation_cache_dir', before)


def test_op_metadata_is_part_of_the_cache_key(monkeypatch, tmp_path):
  """Scopes renamed over the same arithmetic must not come back from the
  cache under their old names (the capture summary bills by them):
  wherever the cache lives, the key covers the metadata."""
  flag = 'jax_compilation_cache_include_metadata_in_key'
  before = getattr(jax.config, flag)
  try:
    for placed in (str(tmp_path), None):
      jax.config.update(flag, False)
      if placed:
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', placed)
      else:
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
      compile_cache.use_compile_cache()
      assert getattr(jax.config, flag) is True
  finally:
    jax.config.update(flag, before)
