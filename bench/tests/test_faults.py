"""A run with its timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip, drives the rest of a run
at the CPU's size, and breaks the served answers where they are made."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from bench import run
from bench.tests import tiny

SEED = 2 ** 35 + 11


def _run(serve_wrap, traffic=tiny.OFFLINE):
    return run.run_cell(tiny.cell(traffic), SEED, 1.0, False,
                        time.perf_counter(), serve_wrap=serve_wrap)


def test_sound_run_is_correct():
    assert _run(None)["correct"]


def test_altered_answer_is_caught():
    def wrap(serve):
        def served(Q):
            nn, dist = serve(Q)
            nn = np.array(nn)
            nn[::3] = (nn[::3] + 1) % 300            # a wrong neighbour
            return nn, dist
        return served
    r = _run(wrap)
    assert not r["correct"], r["check"]


def test_altered_distance_is_caught():
    def wrap(serve):
        def served(Q):
            nn, dist = serve(Q)
            return nn, np.asarray(dist) * (1 + 1e-3)
        return served
    assert not _run(wrap)["correct"]


def test_half_batch_left_out_is_caught():
    def wrap(serve):
        def served(Q):
            half = len(Q) // 2
            nn, dist = serve(np.concatenate([Q[:half], Q[:half]]))
            return nn, dist                          # rows past half stale
        return served
    assert not _run(wrap, tiny.OPEN)["correct"]


def test_exchange_between_chips_left_out_is_caught():
    """Four virtual CPU devices: the sharded mesh path with its
    all_gather of per-shard winners replaced by the shard's own."""
    code = textwrap.dedent(f"""
        import sys, time
        sys.path[:0] = {[run.ROOT, os.path.join(run.ROOT, "src")]!r}
        import jax
        from bench import run
        from bench.tests import tiny
        cell = tiny.cell(tiny.OFFLINE, shards=4)
        sound = run.run_cell(cell, {SEED}, 1.0, False, time.perf_counter())
        jax.lax.all_gather = lambda x, axis_name, **kw: x[None]
        broken = run.run_cell(cell, {SEED}, 1.0, False, time.perf_counter())
        print(sound["correct"], broken["correct"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] == ["True", "False"], out.stdout
