"""Canary for the ``_FLAT_RING_MAX = 16`` compiler-behavior constant.

``ring_collectives`` switches to hierarchical rings past 16 devices
because THIS libtpu's async-collective conversion handles a 16-cycle
ppermute chain but lowers the 32-participant case blocking (28/60/0
async pairs counted in the programs compiled for 8/16/32). That is a
property of
the compiler, not of this code: a libtpu upgrade can move the cliff in
either direction and would otherwise only show up as a silent perf
regression. These tests AOT-compile tiny probe programs (no chips
needed, ~3 s each on jax 0.9.0) and fail loudly when the compiler's
behavior no longer matches the constant:

* 16-device flat ring still converts async -> _FLAT_RING_MAX may stay >= 16;
* 32-device flat ring still does NOT -> _FLAT_RING_MAX must stay < 32
  (if this starts passing async, raise the constant and re-measure).
"""

import ast
import os
import subprocess
import sys

import pytest

from acco_tpu.parallel.ring_collectives import _FLAT_RING_MAX

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _probe(case: str):
    # a child process: describing the topology takes libtpu's lock, which
    # this pytest process must leave to the other tpu_aot children
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "tools", "permute_probe.py"),
            "--hops", "4", "--payload-mb", "0.5", "--cases", case,
        ],
        capture_output=True, text=True, timeout=600, cwd=_REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # tiny payload + few hops: the schedule structure, not the timing,
    # is under test (the cliff is participant-count-driven, not payload)
    return ast.literal_eval(proc.stdout.strip().splitlines()[-1])


@pytest.mark.tpu_aot
def test_flat_ring_async_at_16_devices():
    r = _probe("cycle16_16d")
    assert r["async_pairs"] > 0 and r["blocking"] == 0, (
        f"16-device flat ring no longer converts async ({r}): the libtpu "
        f"changed behavior — re-measure and lower _FLAT_RING_MAX "
        f"(= {_FLAT_RING_MAX})"
    )


@pytest.mark.tpu_aot
def test_flat_ring_still_blocking_at_32_devices():
    r = _probe("cycle32")
    assert r["async_pairs"] == 0, (
        f"32-device flat ring now converts async ({r}): the libtpu "
        f"improved — raise _FLAT_RING_MAX (= {_FLAT_RING_MAX}) and "
        f"re-run tools/overlap_hlo.py --devices 32"
    )


def test_constant_matches_measured_cliff():
    # the constant itself: 16 in, 32 out (the probes above keep the
    # measured basis honest)
    assert 16 <= _FLAT_RING_MAX < 32
