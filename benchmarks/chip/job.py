"""The job a cell's window runs, through the program's own entry points.

A traffic file names its job; `jobs/<job>.py` holds its class as `JOB`, a
`Job` subclass. The estimator's arguments are the configuration's
`estimator` dict updated by the traffic's, passed to `RankSVM` unchanged.

Each job keeps what the window produced (bundle states, iterates,
objectives) as `Record`s for the comparison with the reference after the
window.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import gen
import spec


@dataclasses.dataclass
class Window:
    seconds: float
    counts: dict            # 'iterations', 'models'
    iters_per_model: list   # BMRM iterations of each model finished


@dataclasses.dataclass
class Record:
    """One model's output as host arrays, for the reference."""
    lam: float
    eps: float
    w_best: np.ndarray      # the model's weights
    objective: float        # J(w_best) as the program reports it
    A: np.ndarray | None = None      # (P, n) planes of the bundle
    b: np.ndarray | None = None
    S: np.ndarray | None = None      # (P, n) iterate each plane was cut at
    alpha: np.ndarray | None = None  # (P,) bundle dual
    w: np.ndarray | None = None      # last iterate, -A^T alpha / (2 lam)
    gap: float | None = None         # J(w_best) - D(alpha), as stored
    done: bool = False               # the program says gap < eps
    must_converge: bool = False      # the job promised a fit to eps
    problem: int = 0                 # index of its data set in Job.problems


def state_record(lam: float, eps: float, state, problem: int = 0,
                 must_converge: bool = False) -> Record:
    """A Record of a device `BundleState` (its active planes)."""
    n = int(state.n_active)
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    return Record(lam=float(lam), eps=float(eps), w_best=f64(state.w_best),
                  objective=float(state.j_best), A=f64(state.A)[:n],
                  b=f64(state.b)[:n], S=f64(state.S)[:n],
                  alpha=f64(state.alpha)[:n], w=f64(state.w),
                  gap=float(state.gap), done=bool(state.done),
                  must_converge=must_converge, problem=problem)


class Job:
    """Data, set-up and window of one cell."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.problems, self.order, self.data = [], [], None

    def estimator(self) -> dict:
        """RankSVM's arguments: the configuration's, then the traffic's."""
        return {**self.config.get('estimator', {}),
                **self.traffic.get('estimator', {})}

    def generate(self):
        """The cell's data sets and the order the window takes them in.

        A configuration with `problems: k` is k fixed data sets (drawn
        from `data_seed`, `data_seed` + 1, ...), the same in every run, and
        the run's seed orders them: every run then does the same work, as
        fits to eps on data of one law differ in their iterations. Without
        it the seed draws the one data set. `data` is the first in order.
        """
        k = int(self.config.get('problems', 0))
        if k:
            base = int(self.config['data_seed'])
            self.problems = [gen.generate(self.config, base + i)
                             for i in range(k)]
            self.order = np.random.default_rng(self.seed).permutation(
                k).tolist()
        else:
            self.problems, self.order = [gen.generate(self.config,
                                                      self.seed)], [0]
        self.data = self.problems[self.order[0]]

    # Readers of per-layer metrics use these three after the window.
    oracle = None          # the program's oracle of the last model
    w = None               # an iterate of the last model (host float32)
    state = None           # the last model's device bundle state

    def setup(self):
        """Data, then whatever warms every program the window runs."""
        raise NotImplementedError

    def window(self, seconds: float) -> Window:
        raise NotImplementedError

    def records(self, n_sample: int) -> list:
        """Records of up to `n_sample` models of the window."""
        raise NotImplementedError

    def release(self):
        """Drop the program's state, so the reference has the device."""
        self.oracle = self.state = None


def make(config: dict, traffic: dict, seed: int) -> Job:
    return spec.load_module('jobs', traffic['job']).JOB(config, traffic,
                                                         seed)
