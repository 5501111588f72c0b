"""Trace reduction on a small trace recorded on one TPU v5e chip: three
``bench.step`` spans, each around one call of a jitted function that runs
the paged prefill kernel at a small shape (``bench/testdata``)."""

import numpy as np
import pytest

from bench_cpu_sizes import ROOT
from bench import trace_reduce
from bench.metrics import paged_attn_roofline

TRACE = ROOT / "bench/testdata/paged_prefill_3steps.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(str(TRACE))


def test_planes_and_spans(summary):
    assert summary.devices == 1
    assert len(summary.host["bench.step"]) == 3
    assert summary.host_events


def test_kernel_is_found_by_its_name(summary):
    secs, n = summary.op_seconds(
        lambda o: bool(paged_attn_roofline.KERNEL.search(o.name)))
    assert n == 3
    assert 0 < secs < summary.busy_s


def test_busy_and_idle_tile_the_steps(summary):
    u = summary.busy_union(0)
    lo = min(s for s, _ in summary.host["bench.step"])
    hi = max(e for _, e in summary.host["bench.step"])
    # the device clock of this trace runs about 0.7 ms behind the host's,
    # so each step's ops start just before its span: all lie within 2 ms
    assert u[0, 0] > lo - 2e-3 and u[-1, 1] < hi
    gaps = summary.idle_gaps()
    busy = trace_reduce.covered(u, lo, hi)
    assert sum(e - s for s, e in gaps) + busy == pytest.approx(hi - lo,
                                                               rel=1e-6)


def test_breakdown(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["device_ops"][0][0].startswith("paged_prefill_attention")
    assert b["device_ops"][0][0].endswith("custom-call")
    assert all(len(v) <= 10 for v in b.values())
    assert b["idle_gaps"][0][0] == "bench.step"


def test_short_names():
    assert trace_reduce.short_name(
        "%copy.136 = bf16[32,12007,16,96]{3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[32,12007,16,96]{1,3,2,0} %vp_0_.1)") == \
        "copy.136 bf16[32,12007,16,96] copy"
    assert trace_reduce.short_name(
        "%copy-start = (s32[2]{0:T(128)S(1)}, s32[2]{0:T(128)}, u32[]) "
        "copy-start(s32[2]{0:T(128)} %x.1)") == "copy-start tuple copy-start"
    assert trace_reduce.op_kind("%while.4 = (s32[], bf16[2]) while((s32[]"
                                ") %t), body=%b") == "while"


def test_union_and_cover():
    u = trace_reduce.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert np.allclose(u, [[0.0, 2.0], [3.0, 4.0]])
    assert trace_reduce.covered(u, 1.0, 3.5) == pytest.approx(1.5)
