"""The MultiVic -> TPU bridge: schedule validity, WCET ordering, VMEM
feasibility — time-predictability carried to the target hardware."""
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install repro[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.tpu_mapping import (V5E, tpu_matmul_schedule,
                                    tpu_steady_state, tpu_wcet)


@given(m=st.sampled_from([512, 1024]), k=st.sampled_from([512, 1024]),
       n=st.sampled_from([512, 1024]), nd=st.sampled_from([1, 2, 4]))
@settings(max_examples=15, deadline=None)
def test_tpu_schedule_valid_and_bounded(m, k, n, nd):
    if n % nd:
        return
    sched = tpu_matmul_schedule(m, k, n, n_devices=nd)
    sched.validate_dag()
    sched.validate_interference_freedom()
    w = tpu_wcet(sched)
    s = tpu_steady_state(sched)
    assert 0 < s <= w    # overlap estimate never exceeds the bound


def test_vmem_feasibility_reported():
    sched = tpu_matmul_schedule(4096, 8192, 4096, tile_m=512, tile_n=512)
    assert sched.meta["vmem_need"] <= V5E.vmem_bytes
    assert sched.meta["vmem_ok"]


def test_wcet_scales_down_with_devices():
    one = tpu_wcet(tpu_matmul_schedule(2048, 2048, 2048, n_devices=1))
    four = tpu_wcet(tpu_matmul_schedule(2048, 2048, 2048, n_devices=4))
    # DMA is shared (the paper's serialized management DMA) but compute
    # parallelizes: 4 devices must be meaningfully faster
    assert four < one


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v5 lite"),
                                           ("cpu", "cpu")])
def test_chip_table_resolves_known_devices(platform, kind):
    """A listed TPU kind gets its own peaks; a CPU host prices the
    bound against the v5e target, as the validation runs print it."""
    from types import SimpleNamespace

    from repro.core.tpu_mapping import CHIPS, chip_for
    chip = chip_for(SimpleNamespace(platform=platform, device_kind=kind))
    assert chip is V5E
    assert CHIPS["TPU v5 lite"].peak_flops == 197e12
    assert CHIPS["TPU v5 lite"].hbm_bw == 819e9


def test_chip_table_unknown_tpu_kind_raises():
    """A TPU missing from the table is an error, never a silent v5e."""
    from types import SimpleNamespace

    from repro.core.tpu_mapping import chip_for
    with pytest.raises(ValueError, match="TPU v99"):
        chip_for(SimpleNamespace(platform="tpu", device_kind="TPU v99"))
