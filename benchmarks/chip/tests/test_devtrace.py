"""The trace reduction on hand-built traces."""

import pytest

from devtrace import Event, Trace, merge, reduce, short_name


def _trace(devices):
    host = [Event(0.0, 10.0, 'bench.window'), Event(2.0, 4.5, 'fit'),
            Event(2.5, 3.5, 'oracle build'), Event(9.2, 9.4, 'sync')]
    return Trace(devices, host)


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [Event(-1.0, 2.0, 'fusion.1'), Event(1.0, 2.0, 'fusion.2'),
           Event(4.5, 9.0, 'sort.3'), Event(11.0, 12.0, 'after')]
    r = reduce(_trace({'/device:TPU:0': ops}))
    assert r['window_s'] == 10.0
    assert r['busy_s'] == pytest.approx(2.0 + 4.5)
    assert r['idle_pct'] == pytest.approx(35.0)
    # fusion.2 lies inside fusion.1: each counts its own time.
    assert dict(r['breakdown']['device_ops']) == pytest.approx(
        {'sort.3': 4.5, 'fusion.1': 1.0, 'fusion.2': 1.0})


def test_idle_gaps_are_named_by_the_innermost_open_host_span():
    ops = [Event(0.0, 2.0, 'a'), Event(4.5, 9.0, 'b')]
    gaps = reduce(_trace({'/device:TPU:0': ops}))['breakdown']['idle_gaps']
    assert gaps[0] == ['oracle build', pytest.approx(2.5)]
    assert gaps[1] == ['host idle', pytest.approx(1.0)]


def test_busy_is_averaged_over_devices():
    r = reduce(_trace({'/device:TPU:0': [Event(0.0, 10.0, 'x')],
                       '/device:TPU:1': [Event(0.0, 5.0, 'x')]}))
    assert r['busy_s'] == pytest.approx(7.5)
    assert r['idle_pct'] == pytest.approx(25.0)


def test_a_trace_with_no_device_op_reads_no_idle_share():
    r = reduce(_trace({}))
    assert r['idle_pct'] is None and r['busy_s'] == 0.0


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        reduce(Trace({}, [Event(0.0, 1.0, 'other')]))


def test_merge_clips_and_joins_touching_intervals():
    evs = [Event(0.0, 1.0, 'a'), Event(1.0, 2.0, 'b'), Event(3.0, 7.0, 'c')]
    assert merge(evs, 0.5, 5.0) == [[0.5, 2.0], [3.0, 5.0]]


def test_nested_operations_count_their_own_time_only():
    ops = [Event(0.0, 8.0, 'while.1'), Event(1.0, 3.0, 'fusion.2'),
           Event(4.0, 7.0, 'sort.3'), Event(5.0, 6.0, 'copy.4')]
    r = reduce(_trace({'/device:TPU:0': ops}))
    assert r['busy_s'] == pytest.approx(8.0)
    got = dict(r['breakdown']['device_ops'])
    assert got == pytest.approx({'while.1': 3.0, 'fusion.2': 2.0,
                                 'sort.3': 2.0, 'copy.4': 1.0})


def test_operations_are_named_without_their_operands():
    assert short_name('%fusion.12 = f32[8]{0} fusion(f32[8] %p), '
                      'kind=kLoop, calls=%fc') == 'fusion.12 f32[8]{0} kLoop'
    assert short_name('%sort.3 = s32[64]{0} sort(s32[64] %a)') == \
        'sort.3 s32[64]{0}'
