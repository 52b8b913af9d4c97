"""No TPU, no result; the peaks table knows only what it lists."""

import os
import shutil
import subprocess
import sys
import types

import pytest

import device
import spec

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = spec.repo_root(CHIP)
ARGS = ['--workload', 'cadata-fit', '--seed', str(2 ** 40 + 1),
        '--seconds', '1', '--trace', '0']


def _fake(platform, n):
    return [types.SimpleNamespace(platform=platform, device_kind='x')
            for _ in range(n)]


def test_require_tpu_refuses_another_platform_or_too_few_chips():
    with pytest.raises(device.NoAccelerator):
        device.require_tpu(1, _fake('cpu', 8))
    with pytest.raises(device.NoAccelerator):
        device.require_tpu(4, _fake('tpu', 1))
    assert len(device.require_tpu(1, _fake('tpu', 4))) == 1


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('PYTHONPATH', None)
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_exits_non_zero_and_prints_no_result_without_a_tpu():
    out = _run(ROOT, os.path.join('benchmarks', 'chip', 'run.py'))
    assert out.returncode == 1
    assert out.stdout.strip() == ''
    assert 'needs a TPU' in out.stderr


def test_run_fails_in_a_directory_holding_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    shutil.copytree(CHIP, tmp_path / 'benchmarks' / 'chip')
    out = _run(str(tmp_path), os.path.join('benchmarks', 'chip', 'run.py'))
    assert out.returncode != 0
    assert out.stdout.strip() == ''


def test_peaks_are_keyed_by_device_kind():
    v5e = device.peaks('TPU v5 lite')
    assert v5e['bf16_flops_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9
    assert v5e['hbm_bytes'] == 16e9
    with pytest.raises(KeyError):
        device.peaks('TPU v99')
