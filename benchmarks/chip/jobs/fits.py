"""Back-to-back cold `RankSVM.fit` calls; each model pays its estimator and
oracle construction, as users do."""

from __future__ import annotations

import time

import numpy as np

from job import Job, Window, state_record


class Fits(Job):
    def __init__(self, config, traffic, seed):
        super().__init__(config, traffic, seed)
        self._done = []

    def _one(self, data, **override):
        from repro.core.ranksvm import RankSVM
        svm = RankSVM(**{**self.estimator(), **override})
        svm.fit(data.X, data.y, groups=data.groups)
        return svm

    def setup(self):
        """Data, then one model as the window makes them; with
        `warmup_max_iter` in the traffic, cut to that many iterations (a
        whole chunk: the same compiled programs, less set-up)."""
        self.generate()
        cut = self.traffic.get('warmup_max_iter')
        self._one(self.data, **({} if cut is None else {'max_iter': cut}))

    def window(self, seconds: float) -> Window:
        """Models in the seed's order of the data sets, ending with the first
        whole round over them past `seconds`."""
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            for k in self.order:
                self._done.append((self._one(self.problems[k]), k))
            if time.perf_counter() >= deadline:
                break
        dt = time.perf_counter() - t0
        last = self._done[-1][0]
        self.oracle, self.state = last.oracle_, last.incremental_.state
        self.w = np.asarray(last.w_, np.float32)
        iters = [svm.report_.iterations for svm, _ in self._done]
        return Window(dt, {'iterations': int(sum(iters)),
                           'models': len(iters)}, iters)

    def records(self, n_sample: int) -> list:
        """Up to `n_sample` models of the window, drawn from the seed, the
        last always among them; each was promised a fit to eps."""
        rng = np.random.default_rng(self.seed)
        k = len(self._done)
        pick = sorted(set(rng.choice(k - 1, size=min(n_sample - 1, k - 1),
                                     replace=False).tolist()) | {k - 1}) \
            if k > 1 else [0]
        return [state_record(svm.lam, svm.eps, svm.incremental_.state, p,
                             must_converge=True)
                for svm, p in (self._done[i] for i in pick)]

    def release(self):
        super().release()
        self._done = []


JOB = Fits
