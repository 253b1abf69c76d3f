"""Each cell that replays a search (its traffic has ``rates_gbps``, its
driver's ``Cell`` an ``experiment_config``) is the search it says it is:
replaying its schedule of rates through that function with
``engine="epoch"`` (which plans the same regime as the device engine, with
numpy), the ramp-and-bisect rule applied to the trials' outcomes visits
exactly those rates.  Every trial stays on the epoch fast path, those that
drop too: the planner's full-ring clip drops the arrivals that find a ring
full, as the per-event loop would."""
import pytest

from perfbench import spec
from repro.core import EpochRunInfo
from repro.exp import run_experiment

B = spec.load()


def _experiment_config(wl):
    """The function the cell's driver builds each trial's configuration
    with, or None for a driver that does not replay a search."""
    return getattr(spec.driver(spec.config(B, wl)).Cell, "experiment_config",
                   None)


SIM_CELLS = [w["name"] for w in B["workloads"]
             if "rates_gbps" in spec.traffic(w)
             and _experiment_config(w) is not None]


def search_rates(sustains, start=0.1, refine=4, max_gbps=400.0):
    """The rates a multiplicative ramp then ``refine`` bisection steps
    visit, given ``sustains(rate) -> bool``."""
    seen, good, rate = [], 0.0, start
    while rate <= max_gbps:
        seen.append(rate)
        if not sustains(rate):
            break
        good, rate = rate, rate * 2
    else:
        return seen
    lo, hi = rate / 2, rate
    for _ in range(refine):
        mid = (lo + hi) / 2
        seen.append(mid)
        lo, hi = (mid, hi) if sustains(mid) else (lo, mid)
    return seen


@pytest.mark.parametrize("cell", SIM_CELLS)
def test_schedule_is_the_search_and_its_regime(cell):
    wl = spec.workload(B, cell)
    cfg = dict(spec.config(B, wl), engine="epoch")
    traffic = spec.traffic(wl)
    experiment_config = _experiment_config(wl)
    outcome = {}

    def sustains(rate):
        info = EpochRunInfo()
        rep = run_experiment(experiment_config(cfg, traffic, rate, 0),
                             info=info)
        outcome[round(rate, 1)] = (rep.dropped == 0, info.engine)
        return rep.dropped == 0 and rep.sent > 0

    visited = [round(r, 1) for r in search_rates(sustains)]
    assert visited == traffic["rates_gbps"]
    assert all(engine == "epoch" for _ok, engine in outcome.values()), outcome
    assert sum(not ok for ok, _e in outcome.values()) >= 1
