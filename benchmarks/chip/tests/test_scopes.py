"""The scope reduction on hand-built traces, the HLO op names read from a
real trace's metadata plane, and the sample leaving its job as the window
left it."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import job as job_mod
import scopes
from devtrace import Event
from scopes import Op, Sample, reduce, scope_path

MOD = 'jit_chunk(7)'
OP_NAMES = {MOD: {
    'while.1': 'jit(chunk)/while',
    'fusion.2': 'jit(chunk)/while/body/closed_call/matvec/dot_general',
    'sort.3': 'jit(chunk)/while/body/closed_call/counts/jit(counts_fused)/'
              'sort/jit(argsort)/sort',
    'sort.4': 'jit(chunk)/while/body/closed_call/counts/jit(counts_fused)/'
              'tree/jit(sort)/sort',
    'fusion.5': 'jit(chunk)/while/body/closed_call/counts/mul',
    'sort.6': 'jit(chunk)/while/body/closed_call/qp/jit(sort)/sort',
    'copy.7': '',
}}


def _sample(ops, host=()):
    host = [Event(0.0, 10.0, scopes.SAMPLE_SPAN), *host]
    return Sample({'/device:TPU:0': [Op(s, e, MOD, n) for s, e, n in ops]},
                  host, OP_NAMES)


def test_the_innermost_scope_of_the_op_name_wins_the_primitive_left_out():
    assert scope_path(OP_NAMES[MOD]['sort.3']) == ['counts', 'sort']
    assert scope_path(OP_NAMES[MOD]['sort.4']) == ['counts', 'tree']
    assert scope_path(OP_NAMES[MOD]['sort.6']) == ['qp']
    assert scope_path('jit(chunk)/while') == []
    assert scope_path('matvec') == []        # a primitive, not a scope
    r = reduce(_sample([(0.0, 1.0, 'fusion.2'), (1.0, 3.0, 'sort.3'),
                        (3.0, 6.0, 'sort.4'), (6.0, 6.5, 'fusion.5'),
                        (6.5, 7.0, 'sort.6')]))
    assert r['scope_s'] == pytest.approx(
        {'matvec': 1.0, 'sort': 2.0, 'tree': 3.0, 'counts': 0.5, 'qp': 0.5})
    # 'counts' holds its inner scopes at any depth.
    assert r['inclusive_s'] == pytest.approx(
        {'matvec': 1.0, 'sort': 2.0, 'tree': 3.0, 'counts': 5.5, 'qp': 0.5})


def test_nested_ops_count_their_own_time_and_the_loop_is_unscoped():
    r = reduce(_sample([(0.0, 8.0, 'while.1'), (1.0, 3.0, 'fusion.2'),
                        (4.0, 7.0, 'sort.3'), (5.0, 6.0, 'copy.7')]))
    assert r['busy_s'] == pytest.approx(8.0)
    # while.1 less its children: 8 - 2 - 3; copy.7 has no op_name.
    assert r['scope_s'] == pytest.approx(
        {'unscoped': 3.0 + 1.0, 'matvec': 2.0, 'sort': 2.0})
    assert sum(r['scope_s'].values()) == pytest.approx(r['busy_s'])


def test_unknown_modules_and_instructions_are_unscoped():
    ops = [Op(0.0, 1.0, 'jit_other(9)', 'fusion.2'),
           Op(1.0, 2.0, MOD, 'fusion.99'), Op(2.0, 4.0, None, 'fusion.2'),
           Op(4.0, 5.0, MOD, 'fusion.2')]
    r = reduce(Sample({'/device:TPU:0': ops},
                      [Event(0.0, 10.0, scopes.SAMPLE_SPAN)], OP_NAMES))
    assert r['scope_s'] == pytest.approx({'unscoped': 4.0, 'matvec': 1.0})
    # The unscoped share of busy time.
    assert r['scope_s']['unscoped'] / r['busy_s'] == pytest.approx(0.8)


def test_idle_is_split_by_the_innermost_program_span_jax_events_skipped():
    host = [Event(1.0, 9.0, 'ranksvm.fit'), Event(1.5, 3.0,
                                                  'ranksvm.make_oracle'),
            Event(2.0, 2.5, 'oracle.pairs'), Event(4.0, 5.0, 'bmrm.sync'),
            Event(4.2, 4.8, 'np.asarray(jax.Array)'),
            Event(8.0, 9.5, 'PjitFunction(chunk)')]
    r = reduce(_sample([(3.0, 4.0, 'fusion.2'), (5.0, 8.0, 'sort.3')], host))
    assert r['busy_s'] == pytest.approx(4.0)
    assert r['idle_s'] == pytest.approx({
        'no span': 1.0 + 1.0,              # [0, 1] and [9, 10]
        'ranksvm.fit': 0.5 + 1.0,          # [1, 1.5], [8, 9]
        'ranksvm.make_oracle': 0.5 + 0.5,  # [1.5, 2], [2.5, 3]
        'oracle.pairs': 0.5, 'bmrm.sync': 1.0})
    assert sum(r['idle_s'].values()) == pytest.approx(10.0 - 4.0)
    assert r['span_s'] == pytest.approx({
        'ranksvm.fit': 8.0, 'ranksvm.make_oracle': 1.5,
        'oracle.pairs': 0.5, 'bmrm.sync': 1.0})


def test_a_sample_without_devices_reads_no_device_time():
    r = reduce(Sample({}, [Event(0.0, 1.0, scopes.SAMPLE_SPAN),
                           Event(0.2, 0.4, 'bmrm.sync')], {}))
    assert r['devices'] == 0 and r['busy_s'] == 0.0
    assert r['scope_s'] == {} and r['idle_s'] == {}
    with pytest.raises(ValueError):
        reduce(Sample({}, [Event(0.0, 1.0, 'bench.window')], {}))


def test_op_names_come_from_the_hlo_in_the_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope('counts'):
            with jax.named_scope('sort'):
                o = jnp.argsort(x)
        with jax.named_scope('qp'):
            return jnp.take(x, o) * 2.0

    x = jnp.arange(64.0)[::-1]
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(scopes.SAMPLE_SPAN):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
    s = scopes.load(path)
    assert any(e.name == scopes.SAMPLE_SPAN for e in s.host)
    (names,) = [v for k, v in s.op_names.items() if k.startswith('jit_f(')]
    paths = {tuple(scope_path(v)) for v in names.values()}
    assert ('counts', 'sort') in paths and ('qp',) in paths


class _Toy(job_mod.Job):
    """A job whose window appends models and replaces its state; it
    counts every model it holds, as `jobs/fits.py` does."""

    def setup(self):
        self._done, self.state, self.w = [], ('s', 0), np.zeros(2)
        self.order = [2, 0, 1]

    def window(self, seconds):
        assert self.order == [2, 0, 1]
        k = len(self._done)
        self._done.append(k)
        self.state = ('s', k + 1)
        self.w = np.full(2, k + 1.0)
        return job_mod.Window(0.0, {'iterations': 3 * len(self._done),
                                    'models': len(self._done)}, [3])

    def records(self, n_sample):
        return list(self._done[-n_sample:]) + [self.state]


def test_the_sample_leaves_the_job_as_the_window_left_it(tmp_path):
    job = _Toy({}, {'attempted': 'models'}, 2 ** 40 + 5)
    job.setup()
    job.window(1.0)
    job.window(1.0)
    before = (list(job._done), job.state, job.w.copy(), job.records(4))
    win, r = scopes.take(job, 0.0, str(tmp_path / 'trace'))
    # The sample counts only its own models, not the window's two.
    assert win.counts['models'] == 1
    assert (job._done, job.state, job.records(4)) == \
        (before[0], before[1], before[3])
    np.testing.assert_array_equal(job.w, before[2])
    assert not (tmp_path / 'trace').exists()


def test_per_unit_divides_by_the_samples_own_count():
    win = job_mod.Window(1.0, {'iterations': 4, 'models': 2}, [])
    red = {'seconds': 2.0, 'devices': 1, 'busy_s': 1.0,
           'scope_s': {'qp': 0.4}, 'inclusive_s': {'qp': 0.4},
           'idle_s': {'bmrm.sync': 0.2}, 'span_s': {'bmrm.sync': 0.3}}
    s = scopes.per_unit(win, red, 'models')
    assert s['ms'] == pytest.approx(1000.0) and s['units'] == 2
    assert s['inclusive_s'] == pytest.approx({'qp': 200.0})
    assert s['idle_s'] == pytest.approx({'bmrm.sync': 100.0})
    assert scopes.per_unit(win, red, 'iterations')['busy_ms'] == \
        pytest.approx(250.0)


@pytest.mark.parametrize('workload', ['cadata-fit', 'rcv1-fit'])
def test_the_sample_leaves_a_cells_job_as_its_window_left_it(workload,
                                                             tmp_path):
    from test_correct import _cell
    cell = _cell(workload)
    job = job_mod.make(cell.config, cell.traffic, 2 ** 40 + 9)
    job.setup()
    job.window(0.0)
    attrs = {k: (list(v) if isinstance(v, list) else v)
             for k, v in vars(job).items()}
    recs = job.records(cell.traffic['check_models'])
    scopes.take(job, 0.0, str(tmp_path / 'trace'))
    assert vars(job).keys() == attrs.keys()
    for k, v in vars(job).items():
        if isinstance(v, list):
            assert len(v) == len(attrs[k]) and all(
                a is b for a, b in zip(v, attrs[k])), k
        else:
            assert v is attrs[k], k
    after = job.records(cell.traffic['check_models'])
    assert len(after) == len(recs)
    for a, b in zip(after, recs):
        np.testing.assert_array_equal(a.w_best, b.w_best)
        assert (a.objective, a.gap, a.done) == (b.objective, b.gap, b.done)


STALE = '''
import sys
import jax, jax.numpy as jnp
import devtrace, scopes
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
scoped = sys.argv[1] == 'scoped'


def f(x):
    if scoped:
        with jax.named_scope('qp'):
            return jnp.sort(x) * 2.0
    return jnp.sort(x) * 2.0


g, x = jax.jit(f), jnp.arange(64.0)[::-1]
g(x).block_until_ready()


class Job:
    def window(self, seconds):
        g(x).block_until_ready()


def paths(sample):
    return sorted({scope_ for k, m in sample.op_names.items()
                   if k.startswith('jit_f(') for v in m.values()
                   for scope_ in scopes.scope_path(v)})


load = scopes.load
scopes.load = lambda p: print('sample', paths(load(p))) or load(p)
scopes.take(Job(), 0.0, sys.argv[2])
print('flag', jax.config.jax_compilation_cache_include_metadata_in_key)
'''


def test_the_sample_runs_the_programs_as_named_now_not_as_cached(tmp_path):
    """A program cached before its scopes were added loads from JAX's
    persistent cache with the old op names; the sample compiles anew."""
    import os
    import subprocess
    import sys
    (tmp_path / 'stale.py').write_text(STALE)
    chip = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=chip,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    out = {}
    for mode in ('plain', 'scoped'):
        run = subprocess.run(
            [sys.executable, str(tmp_path / 'stale.py'), mode,
             str(tmp_path / 'trace')], env=env, capture_output=True,
            text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-3000:]
        out[mode] = run.stdout.splitlines()
    assert out['plain'] == ['sample []', 'flag False']
    assert out['scoped'] == ["sample ['qp']", 'flag False']
