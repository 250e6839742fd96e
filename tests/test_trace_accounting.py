"""A live tracer's span accounts for its thread (``utils/trace.py``): CPU
seconds split user / kernel, page faults, context switches, and the whole
process's CPU seconds meanwhile. The no-op tracer does none of it."""

import resource
import threading
import time

import pytest

from sparkrdma_tpu.utils.trace import ACCOUNTING_ARGS, NULL, Tracer

pytestmark = pytest.mark.skipif(
    not hasattr(resource, "RUSAGE_THREAD"),
    reason="RUSAGE_THREAD is Linux's: elsewhere spans carry no accounting")


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _only_event(tracer):
    (event,) = tracer._events
    return event


def _cpu(event):
    return event["args"]["cpu_user_s"] + event["args"]["cpu_sys_s"]


def test_a_spinning_span_is_on_the_cpu():
    tracer = Tracer()
    with tracer.span("t.spin", rows=3):
        _spin(0.05)
    event = _only_event(tracer)
    assert set(event["args"]) == {"rows", *ACCOUNTING_ARGS}
    assert event["args"]["rows"] == 3
    assert _cpu(event) == pytest.approx(event["dur"] * 1e-6, rel=0.2)
    # the process's clock covers this thread's
    assert event["args"]["proc_cpu_s"] >= 0.8 * _cpu(event)


def test_a_sleeping_span_is_off_the_cpu():
    tracer = Tracer()
    with tracer.span("t.sleep"):
        time.sleep(0.05)
    event = _only_event(tracer)
    assert event["dur"] >= 0.05e6
    assert _cpu(event) < 0.005
    assert event["args"]["nvcsw"] >= 1   # it gave the CPU up itself


def test_first_touch_shows_as_minor_faults():
    tracer = Tracer()
    with tracer.span("t.touch"):
        blob = bytearray(32 << 20)
        for at in range(0, len(blob), 4096):
            blob[at] = 1
    args = _only_event(tracer)["args"]
    # 8,192 pages of 4 KiB; half of them leaves room for huge pages at the
    # allocation's edges and a kernel that faults around
    assert args["minflt"] >= 4096
    assert args["majflt"] >= 0


def test_proc_cpu_counts_the_other_threads():
    tracer = Tracer()
    with tracer.span("t.two_threads"):
        other = threading.Thread(target=_spin, args=(0.1,))
        other.start()
        time.sleep(0.01)
        other.join(timeout=30)
        assert not other.is_alive()
    event = _only_event(tracer)
    # this thread slept and joined; the spinning was the other's
    assert event["args"]["proc_cpu_s"] > _cpu(event)
    assert event["args"]["proc_cpu_s"] > 0.05


@pytest.mark.parametrize("name", ACCOUNTING_ARGS)
def test_a_reserved_arg_name_is_refused_at_the_call(name):
    tracer = Tracer()
    with pytest.raises(ValueError, match=name):
        with tracer.span("t.reserved", **{name: 1}):
            pytest.fail("the block ran")
    assert tracer._events == []


def test_the_null_tracer_yields_the_callers_dict_untouched():
    before = len(NULL._events)
    with NULL.span("t.null", rows=7, minflt=1) as args:
        args["bytes"] = 9
    assert args == {"rows": 7, "minflt": 1, "bytes": 9}
    assert len(NULL._events) == before == 0


def test_a_childs_cpu_is_no_more_than_its_parents():
    tracer = Tracer()
    with tracer.span("t.parent"):
        _spin(0.01)
        with tracer.span("t.child"):
            _spin(0.02)
        _spin(0.01)
    child, parent = tracer._events   # a span is recorded at its end
    assert (child["name"], parent["name"]) == ("t.child", "t.parent")
    for key in ACCOUNTING_ARGS:
        assert child["args"][key] <= parent["args"][key], key
    assert _cpu(parent) >= 0.03


def test_the_other_event_kinds_carry_no_accounting():
    tracer = Tracer()
    tracer.complete_span("t.complete", "shuffle", 0.0, 5.0, rows=1)
    tracer.instant("t.instant", rows=1)
    tracer.counter("t.counter", 2.0)
    for event in tracer._events:
        assert not set(event["args"]) & set(ACCOUNTING_ARGS), event["name"]
