"""A whole benchmark run, tiny, on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath,
``correct`` comes out false, once for each fault the cells can have."""

import time

import jax
import jax.numpy as jnp
import pytest

from mpi_cuda_process_tpu import driver
from mpi_cuda_process_tpu.parallel import halo, stepper
from mpi_cuda_process_tpu.utils import diagnostics
from perfbench import cell, check, spec

TINY = [32, 32, 128]
SEED = 2**31 + 1234


@pytest.fixture(autouse=True)
def _follow_two(monkeypatch):
    # the warm chunk and one window chunk: a short window holds one
    monkeypatch.setattr(check, "FOLLOW", 2)


def _run(workload, seed=SEED):
    bench = spec.benchmark()
    w = spec.workload(bench, workload)
    config = dict(spec.config(bench, w["config"]), grid=TINY)
    traffic = spec.traffic(w["traffic"])
    record = cell.run(config, traffic, seed, 0.05,
                      jax.devices()[:w["chips"]], time.perf_counter(),
                      say=lambda msg: None)
    assert record["chunk_s"] and record["diags"]
    return cell.verify(record, jax.devices()[0])


@pytest.mark.parametrize("workload", ["heat3d-1024.log16",
                                      "heat3d-1024-2x2.log8"])
def test_sound_run_is_correct(workload):
    correct, checks = _run(workload)
    assert correct, checks


def _unchanged(real):
    def make(step_fn, n, jit=True):
        return jax.jit(lambda fs: tuple(f + 0 for f in fs))
    return make


def _half_left_out(real):
    def make(step_fn, n, jit=True):
        inner = real(step_fn, n, jit=False)

        def run(fs):
            new = inner(fs)
            rows = fs[0].shape[0]
            keep = (jnp.arange(rows) < rows // 2)[:, None, None]
            return tuple(jnp.where(keep, a, b) for a, b in zip(new, fs))
        return jax.jit(run, donate_argnums=0)
    return make


def _cell_altered(real):
    def make(step_fn, n, jit=True):
        inner = real(step_fn, n, jit=False)

        def run(fs):
            new = inner(fs)
            return (new[0].at[9, 7, 5].add(0.5),) + tuple(new[1:])
        return jax.jit(run, donate_argnums=0)
    return make


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _cell_altered])
def test_broken_runner_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(driver, "make_runner", fault(driver.make_runner))
    correct, checks = _run("heat3d-1024.log16")
    assert not correct, checks


def test_altered_answer_is_not_correct(monkeypatch):
    real = diagnostics.field_diagnostics

    def altered(st, fields, step_fn=None):
        d = real(st, fields, step_fn=step_fn)
        d["mean"] *= 1.001
        return d

    monkeypatch.setattr(diagnostics, "field_diagnostics", altered)
    correct, checks = _run("heat3d-1024.log16")
    assert not correct
    assert checks["diag_rel_gap"]["value"] > checks["diag_rel_gap"]["limit"]


def test_nan_answer_is_not_correct(monkeypatch):
    real = diagnostics.field_diagnostics

    def nan(st, fields, step_fn=None):
        return dict(real(st, fields, step_fn=step_fn), mean=float("nan"))

    monkeypatch.setattr(diagnostics, "field_diagnostics", nan)
    correct, checks = _run("heat3d-1024.log16")
    assert not correct, checks


def test_exchange_left_out_is_not_correct(monkeypatch):
    def no_exchange(x, axis_names, counts, halo_w, bc, periodic=False):
        # every face padded locally with the wall value: no ppermute
        return halo.exchange_and_pad(x, [None] * x.ndim, [1] * x.ndim,
                                     halo_w, bc, periodic)

    monkeypatch.setattr(stepper, "exchange_and_pad", no_exchange)
    correct, checks = _run("heat3d-1024-2x2.log8")
    assert not correct
    assert checks["state_plane_gap"]["value"] > 1.0
