"""The ``exec_overlap_share`` reader: the share of EXEC records dispatched
with an earlier EXEC of their device in flight."""
from types import SimpleNamespace

import pytest

from bench import spec
from bench.tests.conftest import small_cell


@pytest.fixture(scope="module")
def reader():
    return spec.metric_reader(small_cell("sparselu-bots-b100.1chip"),
                              "exec_overlap_share")


def test_records_with_inflight_give_the_share(reader):
    recs = [SimpleNamespace(seconds=1.0, inflight=n) for n in (0, 1, 3, 0)]
    assert reader.read(SimpleNamespace(compute=recs)) == pytest.approx(0.5)
    alone = [SimpleNamespace(seconds=1.0, inflight=0)] * 3
    assert reader.read(SimpleNamespace(compute=alone)) == 0.0


def test_records_without_the_field_give_nothing(reader):
    # the records of a program that counts no EXECs in flight
    bare = [SimpleNamespace(seconds=1.0)] * 2
    assert reader.read(SimpleNamespace(compute=bare)) is None
    assert reader.read(SimpleNamespace(compute=[])) is None
