"""The data-sharded run on four virtual CPU devices: sound, it is
correct; with the cross-chip exchange of the stat fold left out, it is
not. Runs in a child process so that the four devices exist whatever
this process's JAX was started with."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

CHILD = r"""
import json, sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {tests!r})
import jax
import run
from chipbench_tiny import tiny_cell, exchange_left_out
assert len(jax.devices()) == 4, jax.devices()
cell = tiny_cell(chips=4)
prep = run.prepare(cell, 2 ** 32 + 5)
got = {{}}
for name, patch in (("sound", None), ("exchange_left_out",
                                      exchange_left_out())):
    line = run.run_cell(cell, 0, 0.1, False, jax.devices(),
                        window_patch=patch, prep=prep, say=lambda m: None)
    got[name] = [line["correct"],
                 [r[0] for r in line["_rows"] if not r[3]]]
print(json.dumps(got))
"""


def test_leaving_out_the_exchange_between_chips_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(bench=str(HERE.parent), tests=str(HERE))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["sound"] == [True, []]
    correct, failed = got["exchange_left_out"]
    assert not correct and "stats_gap" in failed, got
