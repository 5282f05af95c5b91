"""utils/device.py: the compile-cache placement every entry point shares,
the device identity a `jax://` process reports, and the peak table."""

import os

import jax
import pytest

from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import create_endpoint
from spicedb_kubeapi_proxy_tpu.utils import device
from spicedb_kubeapi_proxy_tpu.utils import metrics as m


class TestCompileCache:
    def test_set_variable_is_left_alone(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        assert device.configure_compile_cache() == str(tmp_path)
        assert updates == []

    def test_unset_variable_points_into_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        want = os.path.join(device.REPO_ROOT, ".jax_cache")
        assert device.configure_compile_cache() == want
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
        # jax is already imported here, so its config follows too
        assert updates == [("jax_compilation_cache_dir", want)]


class TestDeviceInfo:
    def test_announce_exports_platform_kind_and_count(self):
        reg = m.Registry()
        info = device.announce(reg)
        assert info == {"platform": "cpu",
                        "device_kind": jax.devices()[0].device_kind,
                        "count": len(jax.devices())}
        text = reg.render()
        assert (f'authz_device_info{{platform="cpu",device_kind='
                f'"{info["device_kind"]}"}} {info["count"]}') in text

    def test_jax_endpoint_reports_its_device(self, caplog):
        with caplog.at_level("INFO", logger="spicedb_kubeapi_proxy_tpu"):
            create_endpoint("jax://?dispatch=direct")
        assert "serving jax:// from" in caplog.text
        assert 'authz_device_info{platform="cpu"' in m.REGISTRY.render()


class TestPeakTable:
    def test_v5e_peak(self):
        assert device.hbm_peak_gbps("TPU v5 lite") == 819.0

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
    def test_unknown_kind_has_no_peak(self, kind, caplog):
        with caplog.at_level("INFO", logger="spicedb_kubeapi_proxy_tpu"):
            assert device.hbm_peak_gbps(kind) is None

    def test_timeline_gives_no_roofline_on_an_unknown_kind(self):
        from spicedb_kubeapi_proxy_tpu.utils.timeline import Timeline
        tl = Timeline(registry=m.Registry())
        tl.record("kernel", "device", 0.0, 1.0, nbytes=1000)
        assert tl.hbm_peak_bytes_per_s() == 0.0  # cpu: not in the table
        assert tl.summary()["roofline_fraction"] is None
