"""Finds everything one cell needs by the names in `BENCHMARK.json`.

  configs/<file named in BENCHMARK.json>   the deployment: sizes, estimator,
                                           guarantees, its generator's name
  generators/<generator>.py                `generate(cfg, seed)` -> gen.Data
  traffic/<traffic>.json                   the job the window runs and its
                                           parameters
  jobs/<job>.py                            `JOB`, a job.Job subclass
  limits/<workload>.json                   the limit of each number compared
  references/<loss>.py                     `Reference`, the plain reference
  end_to_end/<metric>.py                   `read(ctx)` of an end-to-end metric
  metrics/<metric>.py                      `read(ctx)` of a per-layer metric;
                                           metrics that differ only in the
                                           part after the first '.' may share
                                           `metrics/<part before it>.py`

A cell, a configuration, a traffic mix, a job, a generator or a metric is
added by new files and new `BENCHMARK.json` entries; nothing here or in
the harness names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def repo_root(here: str = HERE) -> str:
    """The checkout's root: the nearest parent holding BENCHMARK.json."""
    d = here
    while True:
        if os.path.isfile(os.path.join(d, 'BENCHMARK.json')):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError('no BENCHMARK.json above ' + here)
        d = parent


def load_module(kind: str, name: str, here: str = HERE):
    """The module `<here>/<kind>/<name>.py`, loaded once per path."""
    path = os.path.join(here, kind, name + '.py')
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(here, kind))
                       if f.endswith('.py'))
        raise KeyError(f'no {kind} named {name!r}; known: {known}')
    key = 'chip_' + ''.join(c if c.isalnum() else '_'
                            for c in os.path.abspath(path)[:-3])
    if key not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[key] = mod
        try:
            mod_spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


@dataclasses.dataclass
class Reader:
    """A metric: its BENCHMARK.json entry and its `read(ctx)`."""
    name: str
    unit: str
    entry: dict
    read: object


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list     # Readers of the end-to-end metrics it reports
    per_layer: list      # Readers of the per-layer metrics it reports


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, workload: str) -> bool:
    return 'workloads' not in entry or workload in entry['workloads']


def load_reader(entry: dict, kind: str = 'metrics',
                here: str = HERE) -> Reader:
    """The reader of one metric: `<kind>/<name>.py`, else the shared
    `<kind>/<name up to its first '.'>.py`."""
    name = entry['name']
    own = os.path.join(here, kind, name + '.py')
    mod = load_module(kind, name if os.path.isfile(own)
                      else name.split('.')[0], here)
    return Reader(name, entry['unit'], entry, mod.read)


def load_cell(workload: str, root: str | None = None,
              here: str = HERE) -> Cell:
    root = root or repo_root(here)
    bench = _load_json(os.path.join(root, 'BENCHMARK.json'))
    cells = {w['name']: w for w in bench['workloads']}
    if workload not in cells:
        raise KeyError(f'unknown workload {workload!r}; known: '
                       f'{sorted(cells)}')
    w = cells[workload]
    configs = {c['name']: c for c in bench['configs']}
    config = _load_json(os.path.join(root, configs[w['config']]['file']))
    traffic = _load_json(os.path.join(here, 'traffic',
                                      w['traffic'] + '.json'))
    limits = _load_json(os.path.join(here, 'limits', workload + '.json'))
    e2e = [load_reader(m, 'end_to_end', here) for m in bench['end_to_end']
           if _applies(m, workload)]
    layer = [load_reader(m, 'metrics', here) for m in bench['per_layer']
             if _applies(m, workload)]
    return Cell(workload, int(w['chips']), config, traffic, limits, e2e,
                layer)
