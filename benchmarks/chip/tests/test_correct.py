"""`correct` on the CPU at small sizes: true for the program as it is,
false with the timed path broken underneath, and false for the control.

Each run goes through `run.run_cell` with everything but the look for a
chip: the cell's own job, window, records and limits, on fewer rows.
"""

import math

import jax
import numpy as np
import pytest

import control
import faults
import run
import spec

SEED = 2 ** 35 + 77
SMALL = {'rcv1-sim': {'m': 3000, 'n': 2000, 'nnz': 3000 * 74},
         'cadata': {'m': 2000}}
CELLS = ('rcv1-fit', 'cadata-fit')


def _cell(workload):
    cell = spec.load_cell(workload)
    cell.config.update(SMALL[cell.config['name']])
    return cell


def _run(workload):
    from repro.core import bmrm as bmrm_mod
    bmrm_mod._SHARED_CHUNKS.clear()
    jax.clear_caches()
    return run.run_cell(_cell(workload), SEED, 0.5, False, jax.devices())


@pytest.fixture(autouse=True)
def _fresh_chunks():
    from repro.core import bmrm as bmrm_mod
    yield
    bmrm_mod._SHARED_CHUNKS.clear()
    jax.clear_caches()

# The limits are set for each cell's own size on the chip; at this size on
# the CPU a sound run reads up to about ten times some of them (grad_rel
# 1.2e-4 in rcv1-fit at m = 3,000, where one flipped pair weighs more;
# qp_rel up to 1.2e-2 there, as 50 planes make a harder QP than the chip's
# 7), yet below what the planted faults read. simplex_err and dual_rel read
# about 1e-7 at any size; a dual value 0.1% high reads 0.1% of D / J, which
# is small in a fit's first iterations.
SOUND_AT_CPU_SIZE = {'loss_rel': 5e-4, 'grad_rel': 5e-4, 'obj_rel': 5e-4,
                     'w_rel': 5e-4, 'simplex_err': 1e-5, 'dual_rel': 1e-5,
                     'qp_rel': 0.05, 'stop_gap': 1.001}


@pytest.mark.parametrize('workload', CELLS)
def test_the_program_reads_near_the_reference(workload):
    res = _run(workload)
    assert all(res['numbers'][k] <= SOUND_AT_CPU_SIZE[k]
               for k in res['numbers']), res['numbers']
    assert set(res['checks']) <= set(res['numbers'])
    assert res['attempted'] >= 1 and res['checked'] >= 1
    assert list(res)[-1] == 'checks'


# The faults each cell can have: those of the QP and the stop show only in
# the numbers that hold them, and a cell holds those only where its why
# names the QP (a window of single RCV1 iterations finishes no fit).
QP_FAULTS = {'qp_uniform': ('qp_rel', 'stop_gap'),
             'dual_inflated': ('dual_rel', 'stop_gap'),
             'alpha_off_simplex': ('simplex_err',),
             'eps_loose': ('stop_gap',)}
CELL_FAULTS = [(w, f) for w in CELLS for f in sorted(faults.FAULTS)
               if f not in QP_FAULTS
               or set(QP_FAULTS[f]) & set(spec.load_cell(w).limits)]


@pytest.mark.parametrize('workload,fault', CELL_FAULTS)
def test_a_broken_timed_path_is_not_correct(workload, fault):
    with faults.plant(fault):
        res = _run(workload)
    assert not res['correct'], res['checks']
    # ... and by more than a sound run reads at this size.
    assert any(not res['numbers'].get(k, math.inf) <= SOUND_AT_CPU_SIZE[k]
               for k in res['checks']), res['numbers']


@pytest.mark.parametrize('workload', ['rcv1-fit', 'cadata-fit'])
def test_the_control_is_not_correct(workload):
    cell = _cell(workload)
    sound = control.run(cell, SEED, 'highest', 6)
    low = control.run(cell, SEED, 'high', 6)
    assert sound['correct'], sound['numbers']
    assert not low['correct'], low['numbers']


def test_control_products_round_as_stated():
    x = np.float32(1.0 + 2.0 ** -12)
    y = np.float32(3.0)
    assert control.products(x, y, 'highest') == x * y
    assert control.products(x, y, 'bfloat16') == 3.0
    assert control.products(x, y, 'high') == x * y   # lo*hi' keeps the bit
