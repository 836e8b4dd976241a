"""Unit tests for utils (parity: reference test_spark_utils.py)."""

import os
import subprocess

import pytest

from raydp_tpu.utils import divide_blocks, memory_string, parse_memory_size

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_memory_size():
    assert parse_memory_size(1024) == 1024
    assert parse_memory_size("1024") == 1024
    assert parse_memory_size("1024B") == 1024
    assert parse_memory_size("1k") == 1024
    assert parse_memory_size("1KB") == 1024
    assert parse_memory_size("1.5 GB") == int(1.5 * 2**30)
    assert parse_memory_size("2g") == 2 * 2**30
    assert parse_memory_size("1T") == 2**40
    with pytest.raises(ValueError):
        parse_memory_size("12XB")


def test_memory_string_roundtrip():
    for s in ["512MB", "1GB", "300"]:
        assert parse_memory_size(memory_string(parse_memory_size(s))) == \
            parse_memory_size(s)


def _check_equal_share(blocks, world_size, shuffle=False, seed=None):
    import math
    result = divide_blocks(blocks, world_size, shuffle=shuffle, shuffle_seed=seed)
    assert set(result.keys()) == set(range(world_size))
    expected = math.ceil(sum(blocks) / world_size)
    for rank, selected in result.items():
        total = sum(n for _, n in selected)
        assert total == expected, f"rank {rank} got {total} != {expected}"
        for idx, n in selected:
            assert 0 <= idx < len(blocks)
            assert 0 < n <= blocks[idx]


def test_divide_blocks_even():
    _check_equal_share([10, 10, 10, 10], 2)
    _check_equal_share([10, 10, 10, 10], 4)


def test_divide_blocks_uneven():
    _check_equal_share([7, 3, 11, 2, 5], 2)
    _check_equal_share([7, 3, 11, 2, 5], 3)
    _check_equal_share([1, 1, 1, 100], 3)


def test_divide_blocks_wraparound():
    # more ranks than evenly divisible blocks → wraparound duplication
    _check_equal_share([5, 6, 7], 2)


def test_divide_blocks_shuffle_deterministic():
    a = divide_blocks([4, 5, 6, 7, 8, 9], 3, shuffle=True, shuffle_seed=42)
    b = divide_blocks([4, 5, 6, 7, 8, 9], 3, shuffle=True, shuffle_seed=42)
    assert a == b
    c = divide_blocks([4, 5, 6, 7, 8, 9], 3, shuffle=True, shuffle_seed=7)
    assert a != c or True  # different seed may coincide; just must not raise


def test_divide_blocks_not_enough():
    with pytest.raises(ValueError):
        divide_blocks([5], 2)


def test_compile_cache_dir_env_wins_else_fixed_path_in_the_checkout(
        monkeypatch, tmp_path):
    from raydp_tpu.utils import COMPILE_CACHE_ENV, compile_cache_dir

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv(COMPILE_CACHE_ENV, placed)
    assert compile_cache_dir() == placed
    assert os.environ[COMPILE_CACHE_ENV] == placed
    assert not os.path.exists(placed)       # nothing is set or made in code

    monkeypatch.delenv(COMPILE_CACHE_ENV)
    want = os.path.join(REPO, ".jax_cache")
    # the same path on every call, in every process: never temp/pid/time
    assert compile_cache_dir() == want == os.environ[COMPILE_CACHE_ENV]
    monkeypatch.delenv(COMPILE_CACHE_ENV)
    assert compile_cache_dir() == want and os.path.isdir(want)


def test_compile_cache_is_configured_in_exactly_one_place():
    """``JAX_COMPILATION_CACHE_DIR`` / ``jax_compilation_cache_dir`` is set by
    the one helper; a second setter would move the cache under some runs."""
    hits = subprocess.run(
        ["git", "grep", "-l", "-i", "-e", "jax_compilation_cache_dir", "--",
         "*.py", ":!tests/"], cwd=REPO, capture_output=True, text=True)
    if hits.returncode not in (0, 1):
        pytest.skip("not a git checkout")
    assert hits.stdout.split() == ["raydp_tpu/utils.py"]
