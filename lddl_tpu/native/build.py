"""Build/load the native shared library.

Compiles ``src/*.cpp`` with g++ into ``_lddl_native.<digest>.so`` next to
this file. The digest covers everything that determines the bytes — the
sources, the compiler flags and the CPU that ``-march=native`` resolves
against — so editing the C++ transparently rebuilds, and a tree copied
to a machine with another CPU builds its own library instead of loading
one compiled for the first. A file lock serializes concurrent builders
(many worker processes may race on first use).
"""

import ctypes
import functools
import glob
import hashlib
import os
import platform
import subprocess
import tempfile

_SRC_DIR = os.path.join(os.path.dirname(__file__), 'src')
_FLAGS = ('-O3', '-march=native', '-shared', '-fPIC', '-std=c++17',
          '-pthread')
_LIB_CACHE = {}


def _sources():
  return sorted(glob.glob(os.path.join(_SRC_DIR, '*.cpp')))


@functools.lru_cache(maxsize=None)
def _target_cpu():
  """Identity of the CPU ``-march=native`` compiles for: the machine
  type plus the first processor's model and feature lines of
  ``/proc/cpuinfo`` (``platform.processor()`` where there is none)."""
  lines = [platform.machine()]
  try:
    with open('/proc/cpuinfo', encoding='utf-8') as f:
      for line in f:
        if not line.strip():
          break  # end of the first processor's block
        if line.startswith(('model name', 'flags', 'Features', 'CPU part')):
          lines.append(line.strip())
  except OSError:
    lines.append(platform.processor())
  return '\n'.join(lines)


def _lib_path():
  h = hashlib.sha256()
  for src in _sources():
    with open(src, 'rb') as f:
      h.update(f.read())
  h.update(' '.join(_FLAGS).encode())
  h.update(_target_cpu().encode())
  digest = h.hexdigest()[:12]
  return os.path.join(os.path.dirname(__file__), f'_lddl_native.{digest}.so')


def build_library(verbose=False):
  """Compile if needed; returns the .so path."""
  path = _lib_path()
  if os.path.exists(path):
    return path
  lock = path + '.lock'
  fd = os.open(lock, os.O_CREAT | os.O_RDWR)
  try:
    import fcntl
    fcntl.flock(fd, fcntl.LOCK_EX)
    if os.path.exists(path):
      return path
    with tempfile.TemporaryDirectory(dir=os.path.dirname(path)) as tmp:
      tmp_so = os.path.join(tmp, 'out.so')
      cmd = ['g++', *_FLAGS, '-o', tmp_so, *_sources()]
      if verbose:
        print('building native library:', ' '.join(cmd))
      subprocess.run(cmd, check=True, capture_output=not verbose)
      os.replace(tmp_so, path)  # atomic publish
    return path
  finally:
    os.close(fd)
    try:
      os.unlink(lock)
    except OSError:
      pass


def load_library():
  """Build (if needed) and dlopen the native library; cached per process."""
  path = build_library()
  lib = _LIB_CACHE.get(path)
  if lib is not None:
    return lib
  lib = ctypes.CDLL(path)
  c = ctypes
  lib.lddl_wp_create.restype = c.c_void_p
  lib.lddl_wp_create.argtypes = [
      c.c_char_p, c.POINTER(c.c_int64), c.c_int32, c.c_int32, c.c_int32,
      c.c_int32
  ]
  lib.lddl_wp_destroy.argtypes = [c.c_void_p]
  lib.lddl_wp_encode_batch.restype = c.c_int64
  lib.lddl_wp_encode_batch.argtypes = [
      c.c_void_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int64, c.c_int32,
      c.POINTER(c.c_int32), c.c_int64, c.POINTER(c.c_int64), c.c_int32
  ]
  lib.lddl_split_sentences.restype = c.c_int64
  lib.lddl_split_sentences.argtypes = [
      c.c_char_p, c.c_int64, c.POINTER(c.c_int64), c.c_int64
  ]
  lib.lddl_encode_docs.restype = c.c_int64
  lib.lddl_encode_docs.argtypes = [
      c.c_void_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int64, c.c_int32,
      c.POINTER(c.c_int32), c.c_int64, c.POINTER(c.c_int64), c.c_int64,
      c.POINTER(c.c_int64), c.c_int32
  ]
  lib.lddl_decode_join.restype = c.c_int64
  lib.lddl_decode_join.argtypes = [
      c.c_void_p, c.POINTER(c.c_int32), c.POINTER(c.c_int64), c.c_int64,
      c.c_char_p, c.c_int64, c.POINTER(c.c_int32)
  ]
  lib.lddl_native_abi_version.restype = c.c_int64
  lib.lddl_columnar_sizes.restype = c.c_int64
  lib.lddl_columnar_sizes.argtypes = [
      c.c_void_p, c.c_int32, c.POINTER(c.c_void_p), c.POINTER(c.c_void_p),
      c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.POINTER(c.c_int64),
      c.c_int64, c.POINTER(c.c_int64)
  ]
  lib.lddl_columnar_emit.restype = c.c_int64
  lib.lddl_columnar_emit.argtypes = [
      c.c_void_p, c.c_int32, c.POINTER(c.c_void_p), c.POINTER(c.c_void_p),
      c.POINTER(c.c_int64), c.POINTER(c.c_void_p), c.POINTER(c.c_void_p),
      c.POINTER(c.c_int64), c.POINTER(c.c_uint16), c.POINTER(c.c_int64),
      c.c_int64, c.POINTER(c.c_int64), c.c_char_p, c.c_int32
  ]
  lib.lddl_plan_pairs.restype = c.c_int64
  lib.lddl_plan_pairs.argtypes = [
      c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int64,
      c.POINTER(c.c_uint32), c.POINTER(c.c_int32), c.c_int32, c.c_double,
      c.c_int32, c.POINTER(c.c_int64), c.c_int64
  ]
  lib.lddl_mask_topk.restype = None
  lib.lddl_mask_topk.argtypes = [
      c.POINTER(c.c_uint64), c.POINTER(c.c_int64), c.c_int64, c.c_int64,
      c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int32
  ]
  lib.lddl_mask_partition.restype = None
  lib.lddl_mask_partition.argtypes = [
      c.POINTER(c.c_int32), c.POINTER(c.c_int64), c.POINTER(c.c_int64),
      c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int64),
      c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_uint64, c.c_int32,
      c.c_int32, c.POINTER(c.c_int32), c.POINTER(c.c_int32),
      c.POINTER(c.c_uint16), c.POINTER(c.c_int32), c.c_int32
  ]
  _LIB_CACHE[path] = lib
  return lib


if __name__ == '__main__':
  print(build_library(verbose=True))
