"""repro.core.spans: nothing with the profiler off, nested named events with
their args with it on."""
import glob

import jax
import pytest

from repro.core import spans


class Costly:
    """An arg that counts every time something formats it."""

    def __init__(self):
        self.formatted = 0

    def __str__(self):
        self.formatted += 1
        return "costly"

    __repr__ = __format__ = lambda self, *a: self.__str__()


def _events(logdir):
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                for e in ln.events:
                    if e.name.startswith("repro."):
                        end = e.start_ns + e.duration_ns
                        out.append((e.name, i, e.start_ns, end, {k: v for k, v in e.stats}))
    return out


def test_off_emits_nothing_and_formats_no_arg():
    assert not spans.enabled()
    arg = Costly()
    with spans.span("probe.phase", what=arg) as sp:
        assert sp is None
    with spans.timed("probe.stage", what=arg) as t:
        pass
    assert arg.formatted == 0
    assert t.ms >= 0.0


def test_on_nests_and_carries_args(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert spans.enabled()
        with spans.span("probe.outer", ckpt=7, path="slow") as outer:
            with spans.timed("probe.inner", rows=3) as inner:
                pass
            outer.set_metadata(bytes=4096)
    assert not spans.enabled()
    ev = {name: rest for name, *rest in _events(str(tmp_path))}
    assert set(ev) == {"repro.probe.outer", "repro.probe.inner"}
    l_out, s_out, e_out, a_out = ev["repro.probe.outer"]
    l_in, s_in, e_in, a_in = ev["repro.probe.inner"]
    assert l_out == l_in and s_out <= s_in and e_in <= e_out          # nested on one thread
    assert a_out == {"ckpt": 7, "path": "slow", "bytes": 4096}
    assert a_in == {"rows": 3}
    assert inner.ms == pytest.approx((e_in - s_in) * 1e-6, abs=0.5)
