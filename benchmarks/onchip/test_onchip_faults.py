"""A run with the timed path broken underneath must come out not correct.

Each fault a one-chip cell of this benchmark can have is planted in the
service the harness drives, from the moment the window's driver starts:

* an insert that leaves the table unchanged (acknowledged, never written);
* half of each batch left out: every other lookup answered as a miss
  without being searched;
* an answer altered where it is produced.

(The exchange between chips has no place in a one-chip cell.)
"""

import dataclasses

import numpy as np
import pytest

from onchip import tinybench


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tinybench.make(str(tmp_path_factory.mktemp("tiny")))


class _Broken:
    """Forwards to the service; its subclasses break it once the window's
    driver starts."""

    def __init__(self, svc):
        self._svc = svc
        self.armed = False
        self.n = 0

    def __getattr__(self, name):
        return getattr(self._svc, name)

    def start_driver(self, **kw):
        self.armed = True
        return self._svc.start_driver(**kw)


class _Unchanged(_Broken):
    def append(self, *args, **kw):
        if not self.armed:
            return self._svc.append(*args, **kw)


class _Answered:
    """A future already resolved with ``resp``."""

    done = True

    def __init__(self, resp):
        self._resp = resp

    def result(self, timeout=None):
        return self._resp


class _HalfLeftOut(_Broken):
    def submit(self, name, query, *, k=1, **kw):
        self.n += 1
        if self.armed and self.n % 2:
            from repro.serve.am_service import SearchResponse
            return _Answered(SearchResponse(
                rid=-1, table=name, indices=np.full(k, -1, np.int32),
                distances=np.full(k, np.inf, np.float32),
                exact=np.zeros(k, bool), matched=np.zeros(k, bool)))
        return self._svc.submit(name, query, k=k, **kw)


class _Altered:
    def __init__(self, fut):
        self._fut = fut

    @property
    def done(self):
        return self._fut.done

    def result(self, timeout=None):
        r = self._fut.result(timeout)
        idx = r.indices.copy()
        idx[0] += 1
        return dataclasses.replace(r, indices=idx)


class _AlteredAnswers(_Broken):
    def submit(self, *args, **kw):
        fut = self._svc.submit(*args, **kw)
        return _Altered(fut) if self.armed else fut


@pytest.mark.parametrize("workload,fault", [
    ("tiny_kv.latest", _Unchanged),
    ("tiny_kv.latest", _HalfLeftOut),
    ("tiny_l1.steady", _HalfLeftOut),
    ("tiny_kv.zipf", _AlteredAnswers),
    ("tiny_l1.steady", _AlteredAnswers),
])
def test_fault_is_not_correct(tiny, workload, fault):
    result, err = tinybench.run(tiny, workload, wrap=fault)
    assert not result["correct"]
    failing = {k for k, v in result["checks"].items()
               if v["value"] > v["limit"]}
    assert failing & {"mismatched", "wrong_key"}, result["checks"]
    assert "check mismatched" in err
