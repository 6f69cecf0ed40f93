"""The package tunes glibc's allocator when it is imported (PERF.md, fault
19): buffers of up to 32 MiB come from the heap, the heap grows in large
steps, and glibc's own knobs in the environment win."""

import ctypes
import os
import subprocess
import sys

import pytest

import spark_rapids_tpu


def _mallinfo2(libc):
    class Info(ctypes.Structure):
        _fields_ = [(n, ctypes.c_size_t) for n in (
            "arena ordblks smblks hblks hblkhd usmblks fsmblks uordblks "
            "fordblks keepcost").split()]
    libc.mallinfo2.restype = Info
    return libc.mallinfo2()


@pytest.fixture(scope="module")
def libc():
    lib = ctypes.CDLL(None)
    if not hasattr(lib, "mallopt") or not hasattr(lib, "mallinfo2"):
        pytest.skip("not glibc 2.33+")
    if any(k.startswith("MALLOC_") or k == "GLIBC_TUNABLES"
           for k in os.environ):
        pytest.skip("glibc's knobs are set in the environment")
    lib.malloc.restype = ctypes.c_void_p
    lib.malloc.argtypes = [ctypes.c_size_t]
    lib.free.argtypes = [ctypes.c_void_p]
    return lib


def test_applied_at_import_and_again(libc):
    assert spark_rapids_tpu._tune_host_malloc() is True


@pytest.mark.parametrize("mib", [1, 16, 31])
def test_buffers_up_to_32_mib_come_from_the_heap(libc, mib):
    """By default each of these is an `mmap` of its own (threshold 128 KiB)."""
    before = _mallinfo2(libc).hblks
    p = libc.malloc(mib << 20)
    try:
        assert _mallinfo2(libc).hblks == before
    finally:
        libc.free(p)


def test_the_heap_grows_in_large_steps_and_keeps_its_top(libc):
    p = libc.malloc(8 << 20)
    libc.free(p)
    # what was freed stays at the top of the main heap, past the default
    # trim threshold of 128 KiB
    assert _mallinfo2(libc).keepcost >= 8 << 20


@pytest.mark.parametrize("name", ["MALLOC_ARENA_MAX", "GLIBC_TUNABLES"])
def test_an_operators_own_knobs_win(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env[name] = "2" if name == "MALLOC_ARENA_MAX" \
        else "glibc.malloc.arena_max=2"
    out = subprocess.run(
        [sys.executable, "-c", "import spark_rapids_tpu as s; "
         "print(s._tune_host_malloc())"], env=env, capture_output=True,
        text=True, timeout=300)
    assert out.stdout.strip().splitlines()[-1] == "False", out.stderr[-800:]
