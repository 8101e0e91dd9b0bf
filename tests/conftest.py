"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §4).

The reference runs each test file in a fresh process because MPI can only be
initialized once (`/root/reference/test/runtests.jl:24`); here the grid is
re-initializable, so ordinary pytest works.  Multi-device coverage without
hardware comes from 8 virtual CPU devices — the TPU translation of the
reference's single-process self-neighbor trick plus real multi-rank runs.
"""

import pytest

# Platform and device count must be set before the backend initializes
# (first device use); jax.config works after import.
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)  # reference tests are Float64-heavy


@pytest.fixture(autouse=True)
def _finalize_grid_after_test():
    yield
    import implicitglobalgrid_tpu as igg

    if igg.grid_is_initialized():
        igg.finalize_global_grid()


@pytest.fixture
def fault_injection(monkeypatch):
    """Arm ``IGG_FAULT_INJECT`` for one test and hand back the injector.

    Usage::

        def test_x(fault_injection):
            inj = fault_injection("halo_corrupt:step3:block5")
            ...

    Also wires the injector into `ops.halo`'s post-exchange hook point so
    direct `update_halo` calls see the fault.  Everything is torn down after
    the test (env var, injector cache, halo hook).
    """
    from implicitglobalgrid_tpu.ops import halo as _halo
    from implicitglobalgrid_tpu.utils import resilience

    def arm(spec: str):
        monkeypatch.setenv("IGG_FAULT_INJECT", spec)
        resilience.reset_fault_injector()
        resilience.install_halo_fault_hook()
        return resilience.get_fault_injector()

    yield arm
    _halo.set_post_exchange_hook(None)
    resilience.reset_fault_injector()
