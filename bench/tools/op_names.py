"""Under which names a Pallas kernel's time appears in a profile: runs the
program's flash-attention kernel (``repro.kernels.flash_attention``) at a
test size inside a jitted function with ordinary XLA work around it,
profiles a few calls, and prints for each line of the first TPU plane the
event names with their device time, and the statistics of the kernel's
events, as one JSON object.

    python3 bench/tools/op_names.py --out op_names.json

``trace.extract`` keeps the "XLA Ops" events under the HLO instruction's
result name (the part of the event name before `` = ``), and
``trace.reduce`` sums them by that name as ``op_seconds``; this shows what
that name is for a ``pallas_call``.
"""
import argparse
import collections
import glob
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.flash_attention import ops as fa_ops  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("op_names: no TPU found")
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (1, 4, 512, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 2, 512, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 2, 512, 128), jnp.bfloat16)

    @jax.jit
    def attend(q, k, v):
        return jnp.tanh(fa_ops.flash_attention(q * 2, k, v)) + 1

    attend(q, k, v).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="op-names-")
    jax.profiler.start_trace(log_dir)
    for _ in range(4):
        attend(q, k, v).block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(path)
    out = {"lines": {}, "stats": {}}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            by = collections.defaultdict(float)
            for e in line.events:
                by[e.name] += e.duration_ns * 1e-9
                key = e.name.split(" = ")[0]
                if key not in out["stats"] and line.name == "XLA Ops":
                    out["stats"][key] = [str(s)[:300] for s in e.stats]
            out["lines"][line.name] = dict(by)
    text = json.dumps(out, indent=1)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(text)
    print(text)


if __name__ == "__main__":
    main()
