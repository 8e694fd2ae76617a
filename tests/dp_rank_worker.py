"""Cross-process DP rank worker (subprocess target for test_wide_ep_group).

One engine server + wave-synced loop against a (possibly remote) coordinator —
each OS process plays one LWS pod of the reference's multi-node wide-EP DP
deployment (wide-ep-lws decode.yaml:85-108: --data-parallel-address /
--data-parallel-rpc-port / --data-parallel-start-rank). Rank 0 is the leader
and hosts the coordinator on the given rpc port.
"""

import argparse
import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

from llmd_tpu.engine import EngineConfig  # noqa: E402
from llmd_tpu.engine.dp_group import DPEngineGroup, DPGroupConfig  # noqa: E402
from llmd_tpu.models import get_model_config  # noqa: E402


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--dp-size", type=int, default=2)
    ap.add_argument("--rpc-port", type=int, required=True)
    args = ap.parse_args()

    grp = DPEngineGroup(
        get_model_config("tiny"),
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     max_batch_size=4, prefill_chunk=32),
        DPGroupConfig(dp_size=args.dp_size, dp_size_local=1,
                      dp_start_rank=args.rank, dp_rpc_port=args.rpc_port,
                      port_base=0),
        model_name="llmd-tpu/tiny",
    )
    await grp.start()
    print(f"ENDPOINT {grp.endpoints()[0]}", flush=True)
    await asyncio.Event().wait()  # serve until killed


if __name__ == "__main__":
    asyncio.run(main())
