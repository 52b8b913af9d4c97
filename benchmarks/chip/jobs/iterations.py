"""One BMRM fit stepped `max_iter` iterations at a time, so the window can
end at the first step past its length; a fit that reaches eps is followed
by a new cold one.

The estimator builds the oracle and runs the solver with the two calls
its `fit` makes (`_make_oracle`, then `_solve` warm-started from the
fit's own bundle state), so every estimator argument takes effect as in
`fit`, and the oracle is built once, in set-up.
"""

from __future__ import annotations

import time

import numpy as np

from job import Job, Window, state_record


class Iterations(Job):
    def __init__(self, config, traffic, seed):
        super().__init__(config, traffic, seed)
        self._finished = []

    def setup(self):
        from repro.core.ranksvm import RankSVM
        self.generate()
        self.svm = RankSVM(**self.estimator())
        self.oracle = self.svm._make_oracle(self.data.X, self.data.y,
                                            self.data.groups)
        self._step()                      # compiles the step

    def _step(self) -> int:
        svm = self.svm
        res = svm._solve(self.oracle, svm.lam, state=self.state)
        self.state = res.state
        if res.stats.converged:
            self._finished.append(state_record(
                svm.lam, svm.eps, res.state, self.order[0],
                must_converge=True))
            self.state = None
        return res.stats.iterations

    def window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        iters = 0
        while True:
            iters += self._step()
            if time.perf_counter() >= deadline:
                break
        dt = time.perf_counter() - t0
        if self.state is not None:
            self.w = np.asarray(self.state.w, np.float32)
        return Window(dt, {'iterations': iters,
                           'models': len(self._finished)}, [])

    def records(self, n_sample: int) -> list:
        """The last fit finished in the window, if any, and the one under
        way (not yet due at eps)."""
        recs = list(self._finished[-1:])
        if self.state is not None:
            recs.append(state_record(self.svm.lam, self.svm.eps, self.state,
                                     self.order[0]))
        return recs

    def release(self):
        super().release()
        self._finished = []


JOB = Iterations
