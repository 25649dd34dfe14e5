"""Calibrate SimParams against the paper's headline ratios.

Targets (pointer array, write-intensive, Zipf 0.99):
  * O-SYNC collapse: peak near ~48-64 clients, >=2.7x drop by 512 (Fig 1/2)
  * CIDER / O-SYNC  @512 ~= 6.7x  (Fig 11a)
  * CIDER / ShiftLock @512 ~= 2.0x (Fig 11a)
  * CIDER p99 ~4.2x lower than O-SYNC (Fig 12a)
"""
import itertools

from repro.core.sim import SimParams, make_streams, run_sim
from repro.core.types import SyncMode
from repro.launch.compile_cache import enable_compile_cache
from repro.workloads.ycsb import WORKLOADS

N_KEYS = 1_000_000


def main():
    enable_compile_cache()
    grid = itertools.product(
        [24, 32, 48],        # mn_cap
        [3, 6],              # addr_atomic_cap
    )
    print("cap,addr,osync48,osync512,mcs512,cider512,collapse,cider/osync,cider/mcs,p99_ratio,pess,retries_o,retries_c")
    for cap, addr in grid:
        p = SimParams(n_lanes=1024, ticks=12288, max_ops=2048,
                      mn_cap=cap, addr_atomic_cap=addr)
        streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
        r = {}
        for mode in [SyncMode.OSYNC, SyncMode.MCS, SyncMode.CIDER]:
            for nc in ([48, 512] if mode == SyncMode.OSYNC else [512]):
                r[(mode, nc)] = run_sim(p, mode, streams, nc)
        o48 = r[(SyncMode.OSYNC, 48)].throughput_mops
        o512 = r[(SyncMode.OSYNC, 512)].throughput_mops
        m512 = r[(SyncMode.MCS, 512)].throughput_mops
        c512 = r[(SyncMode.CIDER, 512)].throughput_mops
        c = r[(SyncMode.CIDER, 512)]
        p99r = r[(SyncMode.OSYNC, 512)].p99_us / max(c.p99_us, 1)
        print(f"{cap},{addr},{o48:.2f},{o512:.2f},{m512:.2f},{c512:.2f},"
              f"{o48/max(o512,1e-9):.2f},{c512/max(o512,1e-9):.2f},"
              f"{c512/max(m512,1e-9):.2f},{p99r:.2f},{c.pess_ratio:.3f},"
              f"{r[(SyncMode.OSYNC,512)].retries},{c.retries}", flush=True)


if __name__ == "__main__":
    main()
