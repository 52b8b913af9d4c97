"""Cells, configurations, generators, traffic, jobs, limits, references and
metrics are found by the names in BENCHMARK.json; a new one needs new
files only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import spec

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = spec.repo_root(CHIP)


def _bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


@pytest.mark.parametrize('workload', [w['name'] for w in
                                      _bench()['workloads']])
def test_every_cell_loads_with_its_files(workload):
    cell = spec.load_cell(workload, ROOT)
    entry = {w['name']: w for w in _bench()['workloads']}[workload]
    assert cell.config['name'] == entry['config']
    assert cell.chips == entry['chips']
    assert set(cell.limits) and cell.traffic['job']
    spec.load_module('jobs', cell.traffic['job'])
    spec.load_module('generators', cell.config['generator'])
    names = {m.name for m in cell.end_to_end}
    assert 'setup_s' in names and len(names) >= 2
    assert all(callable(r.read) for r in cell.end_to_end + cell.per_layer)
    assert cell.per_layer


def test_metrics_that_differ_after_the_first_dot_share_a_reader():
    a = spec.load_reader({'name': 'idle_pct.iter', 'unit': '%'})
    b = spec.load_reader({'name': 'idle_pct.fit', 'unit': '%'})
    assert a.read is b.read
    own = spec.load_reader({'name': 'qp_ms.fit', 'unit': 'ms'})
    assert own.read.__module__ != a.read.__module__
    with pytest.raises(KeyError):
        spec.load_reader({'name': 'no_such.fit', 'unit': 'ms'})


TOY_GENERATOR = '''
import numpy as np
import gen


def generate(cfg, seed):
    rng = gen.rng(seed, 9)
    m = int(cfg['m'])
    X = np.round(rng.normal(size=(m, int(cfg['n']))) * 4) / 4
    y = rng.integers(0, 5, m).astype(np.float64)
    return gen.Data(X, y, rng.integers(0, int(cfg['queries']), m))
'''

TOY_JOB = '''
import spec

Fits = spec.load_module('jobs', 'fits').JOB


class OneRound(Fits):
    """One round over the data sets, whatever the window's length."""

    def window(self, seconds):
        return super().window(0.0)


JOB = OneRound
'''


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A new configuration (with query groups and its own generator), a
    traffic mix with its own job, an end-to-end and a per-layer metric:
    files and BENCHMARK.json entries only, run through the harness. The
    estimator's arguments reach RankSVM unchanged and the groups reach the
    fit, else the grouped reference would not agree."""
    chip = tmp_path / 'benchmarks' / 'chip'
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        'tests', '__pycache__'))
    bench = _bench()
    bench['configs'].append({'name': 'toy', 'source': 'a throwaway',
                             'file': 'benchmarks/chip/configs/toy.json',
                             'reduced': [], 'why': 'a throwaway'})
    bench['workloads'].append({'name': 'toy-fit', 'config': 'toy',
                               'traffic': 'toy-mix', 'chips': 1,
                               'why': 'a throwaway cell'})
    bench['end_to_end'].append({'name': 'zz_models', 'unit': 'models',
                                'better': 'higher', 'bound': 0.1,
                                'source': 'host_clock',
                                'workloads': ['toy-fit']})
    bench['per_layer'].append(
        {'name': 'zz_probe.toy', 'unit': 'ms', 'better': 'lower',
         'source': 'host_clock', 'layer': 'bundle QP',
         'moves': 'zz_models', 'workloads': ['toy-fit']})
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(bench))
    (chip / 'configs' / 'toy.json').write_text(json.dumps(
        {'name': 'toy', 'generator': 'toy_grouped', 'm': 90, 'n': 4,
         'queries': 3,
         'estimator': {'lam': 0.05, 'eps': 1e-3, 'method': 'tree',
                       'loss': 'hinge', 'engine': 'blocked'},
         'matmul_precision': 'highest'}))
    (chip / 'generators' / 'toy_grouped.py').write_text(TOY_GENERATOR)
    (chip / 'jobs' / 'one_round.py').write_text(TOY_JOB)
    (chip / 'traffic' / 'toy-mix.json').write_text(json.dumps(
        {'job': 'one_round', 'estimator': {'max_iter': 200},
         'attempted': 'models', 'check_models': 1, 'check_planes': 8}))
    (chip / 'limits' / 'toy-fit.json').write_text(json.dumps(
        {'loss_rel': 1e-5, 'grad_rel': 1e-5, 'obj_rel': 1e-5,
         'stop_gap': 1.001}))
    (chip / 'end_to_end' / 'zz_models.py').write_text(
        'def read(ctx):\n    return ctx.window.counts["models"]\n')
    (chip / 'metrics' / 'zz_probe.toy.py').write_text(
        'def read(ctx):\n    return 42.0\n')

    code = ('import json, sys; sys.path.insert(0, "benchmarks/chip"); '
            'import jax, run, spec; '
            'cell = spec.load_cell("toy-fit"); '
            'print([r.read(None) for r in cell.per_layer]); '
            'res = run.run_cell(cell, 2 ** 40 + 3, 0.1, False, jax.devices())'
            '; print(json.dumps(run._finite(res)))')
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PYTHONPATH=os.path.join(ROOT, 'src'))
    out = subprocess.run([sys.executable, '-c', code], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[-2] == '[42.0]'
    res = json.loads(lines[-1])
    assert res['correct'], res
    assert set(res['metrics']) == {'zz_models', 'setup_s'}
    assert res['metrics']['zz_models']['value'] == 1.0
    # The cells already there do not see the new metrics.
    old = spec.load_cell('cadata-fit', str(tmp_path), here=str(chip))
    assert 'zz_probe.toy' not in {r.name for r in old.per_layer}
    assert 'zz_models' not in {r.name for r in old.end_to_end}


def test_an_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell('no-such-cell', ROOT)
