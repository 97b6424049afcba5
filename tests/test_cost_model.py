"""Paper §2 cost model: the qualitative claims the paper makes must hold."""
import pytest
from hyp_compat import given, settings, st

from repro.core.cost_model import CostModel, HOREKA_A100, TPU_V5E


def model(n_dofs=74e6, hw=HOREKA_A100):
    return CostModel(hw, n_dofs=n_dofs)


def test_oversubscription_is_catastrophic():
    """Paper fig. 7: GPUOSR1 (n_CPU ranks on n_GPU devices) degrades up to
    ~140x vs the repartitioned case."""
    cm = model()
    n_gpu, n_cpu = 4, 64
    t_oversub = cm.T_single(n_cpu, n_gpu)           # 16 ranks per GPU
    t_repart = cm.T_repartitioned(n_cpu, n_gpu)     # alpha = 16
    assert t_oversub / t_repart > 10


def test_undersubscription_wastes_host_parallelism():
    """Paper: n = n_GPU leaves CPU cores idle → assembly slower than with
    repartitioning at the same number of GPUs."""
    cm = model()
    t_under = cm.T_single(4, 4)        # 4 ranks only (GPUURR1)
    t_repart = cm.T_repartitioned(64, 4)
    assert t_repart < t_under


def test_repartition_beats_both_extremes():
    cm = model()
    t_r = cm.T_repartitioned(64, 4)
    assert t_r < cm.T_single(64, 4) and t_r < cm.T_single(4, 4)


def test_optimal_alpha_grows_with_assembly_share():
    """Heavier assembly → larger optimal alpha (more host parallelism)."""
    light = CostModel(HOREKA_A100, n_dofs=74e6, assembly_flops_per_dof=50,
                      assembly_bytes_per_dof=80)
    heavy = CostModel(HOREKA_A100, n_dofs=74e6, assembly_flops_per_dof=2500,
                      assembly_bytes_per_dof=4000)
    a_light = light.optimal_alpha(n_cpu=64, n_gpu=4)
    a_heavy = heavy.optimal_alpha(n_cpu=64, n_gpu=4)
    assert a_heavy >= a_light


def test_device_direct_beats_host_buffer():
    """Paper fig. 9: GPU-aware updates are 25–50% better end-to-end; the
    repartition term itself is >=2x better."""
    cm = model()
    t_dd = cm.t_repartition(64, 4, device_direct=True)
    t_hb = cm.t_repartition(64, 4, device_direct=False)
    assert t_hb > 2 * t_dd


def test_tpu_has_no_oversubscription_penalty():
    cm = model(hw=TPU_V5E)
    assert cm.T_single(64, 4) == pytest.approx(
        cm.t_assembly(64) + cm.t_solver(4))


@settings(max_examples=25, deadline=None)
@given(n_dofs=st.floats(1e6, 5e8), n_gpu=st.sampled_from([2, 4, 8]))
def test_property_repartitioned_never_worse_than_undersub(n_dofs, n_gpu):
    """T(n_AS*, n_LS*) <= T(n_LS*, n_LS*) + T_R — eq. (3) dominance."""
    cm = model(n_dofs=n_dofs)
    t_r = cm.T_repartitioned(16 * n_gpu, n_gpu)
    t_u = cm.T_single(n_gpu, n_gpu) + cm.t_repartition(16 * n_gpu, n_gpu)
    assert t_r <= t_u + 1e-9


def test_dispatch_overhead_amortized_by_scan_roll():
    """The per-step host dispatch term is retired by the StepProgram's
    scan-rolled executor: an n-step window is one launch, so the
    per-timestep share falls as 1/n — and the term never perturbs the
    four calibrated phases or the controller's alpha argmin."""
    cm = model()
    assert cm.t_dispatch(1) == pytest.approx(cm.dispatch_latency)
    assert cm.t_dispatch(8) == pytest.approx(cm.dispatch_latency / 8)
    assert cm.t_dispatch(8) < cm.t_dispatch(1)
    # phases exclude it (it would bias measured-over-modelled calibration)
    ph = cm.predict_phases(64, 4)
    assert cm.T_step(64, 4, steps_per_dispatch=1) == pytest.approx(
        cm.T_repartitioned(64, 4) + cm.dispatch_latency)
    assert cm.T_step(64, 4, steps_per_dispatch=8) < cm.T_step(64, 4)
    assert ph.total == pytest.approx(
        cm.T_repartitioned(64, 4), rel=0.5)  # same family, no dispatch term


def test_pipelined_step_hides_the_shorter_phase():
    """T_pipelined = max(assembly, solve) + repartition-update: overlap
    hides the shorter of the two walls, so it is never worse than the
    serial sum and exactly the serial sum minus min(assembly, solve)."""
    cm = model()
    for n_as, n_ls in ((16, 4), (64, 4), (8, 8)):
        serial = cm.T_repartitioned(n_as, n_ls)
        piped = cm.T_pipelined(n_as, n_ls)
        t_a, t_s = cm.t_assembly(n_as), cm.t_solver(n_ls)
        assert piped == pytest.approx(serial - min(t_a, t_s))
        assert piped <= serial
        # the dispatch-bearing step variant amortizes like the serial one
        assert cm.T_step_pipelined(n_as, n_ls) == pytest.approx(
            piped + cm.dispatch_latency)
        assert cm.T_step_pipelined(n_as, n_ls, steps_per_dispatch=8) < \
            cm.T_step_pipelined(n_as, n_ls)


def test_optimal_alpha_shifts_under_overlap():
    """Once assembly hides behind the solve, pushing alpha further only
    buys update latency: the overlap argmin must never exceed the serial
    argmin, and the pipelined objective at its own argmin beats the
    serial objective at the serial argmin."""
    cm = model()
    a_serial = cm.optimal_alpha(n_cpu=128, n_gpu=4)
    a_piped = cm.optimal_alpha(n_cpu=128, n_gpu=4, pipelined=True)
    assert a_piped <= a_serial
    assert cm.T_pipelined(4 * a_piped, 4) <= \
        cm.T_repartitioned(4 * a_serial, 4)


def test_phase_breakdown_overlapped_provenance():
    """overlapped defaults False (serial provenance), is carried by the
    dataclass, and never changes the time fields' total."""
    from repro.core.cost_model import PhaseBreakdown

    ph = PhaseBreakdown(1.0, 2.0, 3.0, 4.0)
    assert ph.overlapped is False
    po = PhaseBreakdown(1.0, 2.0, 3.0, 4.0, overlapped=True)
    assert po.overlapped is True
    assert po.total == ph.total == pytest.approx(10.0)
    assert PhaseBreakdown.TIME_FIELDS == ("assembly", "update", "halo",
                                          "solve")


def test_hardware_for_prices_by_device_kind():
    """TPU devices are priced from the device_kind table (an unknown kind
    is an error, not a default); other platforms keep the v5e prior."""
    from types import SimpleNamespace

    from repro.core.cost_model import TPU_SPECS, hardware_for

    def dev(platform, kind):
        return SimpleNamespace(platform=platform, device_kind=kind)

    assert hardware_for(dev("tpu", "TPU v5 lite")) is TPU_V5E
    assert set(TPU_SPECS.values()) == {TPU_V5E}
    assert hardware_for(dev("cpu", "cpu")) is TPU_V5E
    with pytest.raises(ValueError, match="TPU v4"):
        hardware_for(dev("tpu", "TPU v4"))
