"""The driver's two entry points (``__graft_entry__.py``): the one-chip
compile check and the multi-chip dry run the README and CI call."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_entry_jits_and_runs():
    sys.path.insert(0, ROOT)
    from __graft_entry__ import entry

    fn, (sales, items) = entry()
    agg, ngroups = jax.block_until_ready(jax.jit(fn)(sales, items))
    n = int(ngroups)
    # WHERE qty > 5 JOIN items USING(item) GROUP BY cat SUM(qty*price)
    qty = np.array(sales["qty"].to_pylist())
    price = np.array(sales["price"].to_pylist())
    cat = np.array(items["cat"].to_pylist())[sales["item"].to_pylist()]
    keep = qty > 5
    want = {int(c): float((qty * price)[keep & (cat == c)].sum())
            for c in np.unique(cat[keep])}
    got = dict(zip(agg["cat"].to_pylist()[:n], agg["sum_rev"].to_pylist()[:n]))
    assert got.keys() == want.keys()
    for c in want:
        assert got[c] == pytest.approx(want[c], rel=1e-9)


@pytest.mark.slow  # 176 s on the CPU: the mesh steps run op by op
def test_dryrun_multichip_passes_in_a_fresh_interpreter():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c",
         "from __graft_entry__ import dryrun_multichip; dryrun_multichip(4)"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.startswith("dryrun_multichip(4): join pairs=512")
