"""Platform-dependent choices, checked on the CPU: the fedagg kernel mode,
the chip smoke script's refusal to run without a TPU, and the persistent
compilation-cache directory the entry points use."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fedagg import fedagg
from repro.utils import xla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestKernelMode:
    @pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                    ("tpu", False)])
    def test_follows_the_platform(self, monkeypatch, platform, interpret):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert fedagg.resolve_interpret() is interpret

    @pytest.mark.parametrize("platform", ["gpu", "rocm", "metal"])
    def test_other_platforms_raise(self, monkeypatch, platform):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        with pytest.raises(RuntimeError, match=platform):
            fedagg.resolve_interpret()

    def test_explicit_mode_wins(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        assert fedagg.resolve_interpret(False) is False
        assert fedagg.resolve_interpret(True) is True

    @pytest.mark.parametrize("kernel", ["norms", "axpy", "norms_batched",
                                        "apply_batched", "norms_q"])
    def test_no_kernel_defaults_to_the_interpreter_on_tpu(
            self, monkeypatch, kernel):
        """With the platform steered to TPU, every pallas_call the kernel
        entry points issue by default is compiled, and the batched row
        schedule takes its compiled (VMEM-budgeted) branch."""
        seen = []

        def fake_pallas_call(body, *, out_shape, interpret, **kw):
            seen.append((interpret, kw.get("grid")))
            outs = out_shape if isinstance(out_shape, list) else [out_shape]
            zeros = [jnp.zeros(s.shape, s.dtype) for s in outs]
            return lambda *args: (zeros if isinstance(out_shape, list)
                                  else zeros[0])

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fedagg.pl, "pallas_call", fake_pallas_call)
        n = 2 * fedagg.BLOCK_ROWS * fedagg.LANES
        x = jnp.zeros((n,))
        xs = jnp.zeros((3, n))
        q = jnp.zeros((n,), jnp.int8)
        s = jnp.zeros((n // fedagg.QBLOCK,))
        {"norms": lambda: fedagg.fedagg_norms(x, x, x),
         "axpy": lambda: fedagg.fedagg_axpy(x, x, jnp.float32(0.5)),
         "norms_batched": lambda: fedagg.fedagg_norms_batched(x, xs, xs),
         "apply_batched": lambda: fedagg.fedagg_apply_batched(
             x, xs, jnp.ones((3,))),
         "norms_q": lambda: fedagg.fedagg_norms_q(x, x, q, s)}[kernel]()
        # two grid steps of BLOCK_ROWS rows: the compiled schedule, not
        # the interpreter's single whole-vector step
        assert seen == [(False, (2,))]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestChipSmoke:
    def test_refuses_to_run_without_a_tpu(self):
        assert jax.devices()[0].platform == "cpu"
        with pytest.raises(SystemExit) as exc:
            _load_chip_smoke().main([])
        # a string code exits with status 1 and names the platform found
        assert isinstance(exc.value.code, str)
        assert "'cpu'" in exc.value.code

    def test_four_chip_option_also_refuses(self):
        with pytest.raises(SystemExit) as exc:
            _load_chip_smoke().main(["--chips", "4"])
        assert "'cpu'" in exc.value.code


class TestCompileCache:
    def test_environment_directory_wins(self, monkeypatch, tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert xla.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets nothing
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_checkout_directory_otherwise(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            first = xla.enable_compile_cache()
            assert first == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
            assert xla.enable_compile_cache() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
