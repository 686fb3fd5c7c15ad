"""Workload generators (Poisson / Arena / MAF) + client-region mixtures."""

import numpy as np
import pytest

from repro.workloads import make_workload
from repro.workloads.arrivals import Request, interarrival_stats


def test_poisson_rate():
    wl = make_workload("poisson", rate_per_s=0.5, seed=1)
    reqs = wl.generate(20_000.0)
    rate = len(reqs) / 20_000.0
    assert abs(rate - 0.5) < 0.05


def test_poisson_sorted_and_bounded():
    reqs = make_workload("poisson", rate_per_s=1.0, seed=2).generate(500.0)
    times = [r.arrival_s for r in reqs]
    assert times == sorted(times)
    assert all(0 <= t < 500.0 for t in times)
    assert all(r.prompt_tokens >= 1 and r.output_tokens >= 1 for r in reqs)


def test_arena_burstier_than_poisson():
    """Fig. 11: Arena interarrivals have higher CV than Poisson (CV=1)."""
    dur = 100_000.0
    arena = make_workload("arena", base_rate_per_s=0.5, seed=3).generate(dur)
    poisson = make_workload("poisson", rate_per_s=0.5, seed=3).generate(dur)
    cv_a = interarrival_stats(arena)["cv"]
    cv_p = interarrival_stats(poisson)["cv"]
    assert cv_a > cv_p
    assert cv_a > 1.1


def test_maf_diurnal():
    wl = make_workload("maf", base_rate_per_s=0.5, seed=4)
    reqs = wl.generate(86_400.0)
    times = np.array([r.arrival_s for r in reqs])
    # compare midnight-ish vs midday-ish rates
    night = ((times > 0) & (times < 3 * 3600)).sum()
    day = ((times > 11 * 3600) & (times < 14 * 3600)).sum()
    assert day > 1.5 * night


def test_determinism():
    a = make_workload("arena", seed=9).generate(5_000.0)
    b = make_workload("arena", seed=9).generate(5_000.0)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]


def test_unique_ids():
    reqs = make_workload("poisson", rate_per_s=1.0, seed=5).generate(100.0)
    ids = [r.id for r in reqs]
    assert len(set(ids)) == len(ids)


# ---------------------------------------------------------------------------
# client-region mixtures
# ---------------------------------------------------------------------------


def test_default_single_region_unchanged():
    reqs = make_workload("poisson", rate_per_s=1.0, seed=5).generate(600.0)
    assert all(r.client_region == "us-west-2" for r in reqs)


@pytest.mark.parametrize("kind", ["poisson", "arena", "maf"])
def test_client_regions_mixture(kind):
    mix = {"us-west-2": 0.5, "eu-central-1": 0.3, "ap-northeast-1": 0.2}
    rate = {"poisson": {"rate_per_s": 1.0}}.get(
        kind, {"base_rate_per_s": 1.0}
    )
    reqs = make_workload(
        kind, seed=5, client_regions=mix, **rate
    ).generate(3600.0)
    seen = {r.client_region for r in reqs}
    assert seen == set(mix)
    # roughly proportional draws (binomial slack)
    frac = sum(r.client_region == "us-west-2" for r in reqs) / len(reqs)
    assert 0.4 < frac < 0.6


def test_client_regions_do_not_perturb_arrivals():
    """The mixture uses its own RNG stream: arrival times and token
    lengths are bit-identical with and without it."""
    base = make_workload("poisson", rate_per_s=1.0, seed=7).generate(3600.0)
    mix = make_workload(
        "poisson", rate_per_s=1.0, seed=7,
        client_regions=["us-west-2", "eu-central-1"],
    ).generate(3600.0)
    assert [r.arrival_s for r in base] == [r.arrival_s for r in mix]
    assert [r.prompt_tokens for r in base] == [r.prompt_tokens for r in mix]
    assert [r.output_tokens for r in base] == [r.output_tokens for r in mix]


def test_client_regions_seeded():
    kw = dict(rate_per_s=1.0, seed=11,
              client_regions={"us-west-2": 0.7, "us-east-1": 0.3})
    a = make_workload("poisson", **kw).generate(1800.0)
    b = make_workload("poisson", **kw).generate(1800.0)
    assert [r.client_region for r in a] == [r.client_region for r in b]


def test_client_regions_validation():
    with pytest.raises(ValueError):
        make_workload("poisson", client_regions={})
    with pytest.raises(ValueError):
        make_workload("poisson", client_regions={"": 1.0})
    with pytest.raises(ValueError):
        make_workload("poisson", client_regions={"us-west-2": -1.0})


def test_client_regions_exercise_rtt_in_lb():
    """Cross-region clients see the RTT term in their e2e latency."""
    from fleet import ZONE, run_fleet, segments
    from repro.cluster.catalog import default_catalog, region_rtt_ms

    region = default_catalog().zone(ZONE).region
    far = Request(arrival_s=300.0, prompt_tokens=10, output_tokens=10,
                  client_region="eu-central-1")
    near = Request(arrival_s=310.0, prompt_tokens=10, output_tokens=10,
                   client_region=region)
    res, (sp_far, sp_near) = run_fleet([far, near])
    assert res.n_completed == 2
    rtt_far = region_rtt_ms("eu-central-1", region) / 1e3
    rtt_near = region_rtt_ms(region, region) / 1e3
    assert rtt_far > rtt_near
    for sp, rtt in ((sp_far, rtt_far), (sp_near, rtt_near)):
        assert sp["rtt_s"] == pytest.approx(rtt)
        (service,) = segments(sp, "service")
        # same tokens, idle replica: equal service, so e2e differs by RTT
        assert sp["e2e_s"] == pytest.approx(
            service["t1_s"] - sp["arrival_s"] + rtt, abs=1e-9
        )
    assert sp_far["e2e_s"] - sp_near["e2e_s"] == pytest.approx(
        rtt_far - rtt_near, abs=1e-9
    )
