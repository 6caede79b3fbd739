"""``benchmarks.kernel_bench`` is a CPU rehearsal: on any other backend
it must refuse to run instead of writing CPU numbers under a TPU's
name."""
import jax
import pytest

from benchmarks import kernel_bench


def test_kernel_bench_refuses_a_non_cpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU rehearsal"):
        kernel_bench.run()
