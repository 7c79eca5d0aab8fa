"""CI smoke for the DSE scaling layer, run under 8 forced host devices.

Launch with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
dse-scale CI job does): tier-1 tests deliberately see the real single
device, so the genuinely multi-device paths — the shard mesh, the
``jax.shard_map`` psum gather check, per-shard ``jax.default_device``
pinning — are exercised here.

Three gates, every one an acceptance criterion of the scaling PR:

  1. **Sharded == unsharded, bitwise**, through ``tests/differential.py``'s
     exact recursive comparator (not a tolerance check).
  2. **Kill-and-resume == uninterrupted, bitwise**: a sweep preempted
     mid-journal resumes from its ``SweepCheckpoint`` and matches; a
     torn journal tail is re-evaluated, not skipped.
  3. **Search front == exhaustive front** on the 24-config reference grid
     shape, within <=50% of the exhaustive full-fidelity evaluations.

The checkpoint files land in ``--ckpt-dir`` (default results/ckpt_smoke) so
CI can upload them as an artifact when the job fails.
"""
from __future__ import annotations

import argparse
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO_ROOT, "tests"))   # differential.py

import jax                                           # noqa: E402

from differential import assert_bitwise_equal_results   # noqa: E402
from repro.core import (                                # noqa: E402
    SweepCheckpoint,
    dlrm_rmc2_small,
    search,
    sweep,
    tpuv6e,
)
from repro.core.search import pareto_front              # noqa: E402

POLICIES = ("spm", "lru", "srrip", "pinning")
GRID = dict(policies=POLICIES, capacities=(1 << 16, 1 << 17, 1 << 18),
            ways=(4, 8), zipf_s=(0.8, 1.0), num_cores=(1, 2), seed=0)
SEARCH_GRID = dict(policies=POLICIES, capacities=(1 << 16, 1 << 17, 1 << 18),
                   ways=(4, 8), zipf_s=0.9, seed=0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", default=os.path.join(_REPO_ROOT, "results",
                                                       "ckpt_smoke"))
    args = ap.parse_args()
    os.makedirs(args.ckpt_dir, exist_ok=True)

    ndev = len(jax.devices())
    if ndev < 2:
        print("dse_scale_smoke needs multiple devices — launch under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8",
              file=sys.stderr)
        return 1
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=2000, dim=128,
                         lookups=4, batch_size=8, num_batches=2)
    hw = tpuv6e()

    # 1. Sharded over all host devices == single-device path, bitwise.
    ref = sweep(wl, hw, **GRID)
    sharded = sweep(wl, hw, devices=ndev, **GRID)
    assert sharded.sharded and sharded.device_count == ndev
    assert_bitwise_equal_results(ref, sharded, "sharded vs unsharded")
    # The fault-free production path must report zero fault telemetry:
    # spurious retries/failovers here are a supervision bug (and would make
    # perf trajectories incomparable).
    assert not sharded.telemetry.any_faults, sharded.telemetry.to_dict()
    print(f"sharded smoke OK: {ref.num_configs} configs "
          f"({ref.distinct_memo_keys} memo keys) on {ndev} host devices, "
          "bitwise identical to the single-device sweep, zero fault "
          "telemetry")

    # 2. Kill-and-resume (sharded, journaled): preempt after 2 rounds, then
    #    resume — bitwise; then tear the journal tail and resume again.
    ckpt_path = os.path.join(args.ckpt_dir, "smoke.ckpt")
    if os.path.exists(ckpt_path):
        os.unlink(ckpt_path)

    class KillAfter(SweepCheckpoint):
        def __init__(self, path, cadence, rounds):
            super().__init__(path, cadence=cadence)
            self.rounds = rounds

        def record(self, slice_id, results):
            if self.rounds <= 0:
                raise KeyboardInterrupt("simulated preemption")
            self.rounds -= 1
            super().record(slice_id, results)

    ck = KillAfter(ckpt_path, cadence=4, rounds=2)
    try:
        sweep(wl, hw, devices=ndev, checkpoint=ck, **GRID)
        raise AssertionError("expected the simulated preemption to fire")
    except KeyboardInterrupt:
        pass
    finally:
        ck.close()
    resumed = sweep(wl, hw, devices=ndev, checkpoint=ckpt_path, **GRID)
    assert 0 < resumed.resumed_keys < resumed.distinct_memo_keys
    assert_bitwise_equal_results(ref, resumed, "kill+resume")
    # Torn tail: chop the last journal line mid-record.
    raw = open(ckpt_path, "rb").read()
    open(ckpt_path, "wb").write(raw[: len(raw) - len(raw.splitlines(True)[-1]) // 2 - 1])
    torn = sweep(wl, hw, devices=ndev, checkpoint=ckpt_path, **GRID)
    assert_bitwise_equal_results(ref, torn, "torn-tail resume")
    print(f"checkpoint smoke OK: resumed {resumed.resumed_keys}/"
          f"{resumed.distinct_memo_keys} keys after simulated kill, "
          "bitwise identical; torn journal tail re-evaluated")

    # 3. Search: exact exhaustive front, <=50% of full evaluations, sharded.
    exhaustive = sweep(wl, hw, **SEARCH_GRID)
    res = search(wl, hw, devices=ndev,
                 checkpoint_dir=os.path.join(args.ckpt_dir, "search"),
                 **SEARCH_GRID)
    want = sorted(e.config.label for e in pareto_front(exhaustive.entries))
    assert res.front_labels() == want, (res.front_labels(), want)
    by_cfg = {e.config: e for e in exhaustive.entries}
    for e in res.pareto:
        mism = e.result.diff(by_cfg[e.config].result)
        assert not mism, (e.config.label, mism)
    assert res.full_evals <= 0.5 * exhaustive.distinct_memo_keys, (
        res.full_evals, exhaustive.distinct_memo_keys)
    print(f"search smoke OK: exact Pareto front ({len(want)} configs) in "
          f"{res.full_evals}/{exhaustive.distinct_memo_keys} full "
          f"evaluations ({res.low_fidelity_evals} low-fidelity)")
    print("dse scale smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
