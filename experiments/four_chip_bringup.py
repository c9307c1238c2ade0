"""PR 21 bring-up on the four-chip v5e host (a 2x2 mesh, one process).

What it establishes, nothing of it a speed:

1. The data-parallel trainer (`vgg` preset), a federated run (`fed`,
   10 clients over 4 devices) and a secure-aggregation run
   (`secure-fed`, 8 clients, k = 2 a device, the Pallas mask kernel)
   finish on four chips through `idc_models_tpu.cli.main`.
2. Placement: from `addressable_shards`, a batch placed the way the
   trainer places it has one slice on each device, parameters placed
   the way it places them are whole on all four, and the stacked client
   axis has k clients on each device; from `memory_stats()`, every
   device's peak grows under every verb — nothing sits on device 0.
3. The n-device result agrees with the 1-device result, by the gates
   `__graft_entry__.dryrun_multichip` runs on the virtual CPU pod
   (`agreement_checks`), here on the real chips — at the default
   matmul precision, and again with full-f32 matmuls.
4. The order of `jax.devices()`, which `mesh.make_mesh` reshapes as it
   comes, against the chips' coordinates.
5. Four one-chip serving replicas built as `serve-cluster` builds them
   each hold their KV caches on their own device.

    chiprun --chips 4 -- python experiments/four_chip_bringup.py

Appends one JSON line to experiments/four_chip_bringup.jsonl (and to
chiprun_out/, which is what comes back from the chip machine).
"""

from __future__ import annotations

import contextlib
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def _peaks(devices) -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def _shards(x) -> dict:
    return {str(s.device.id): list(s.data.shape)
            for s in x.addressable_shards}


def main() -> None:
    import jax
    import numpy as np

    import __graft_entry__ as graft
    from idc_models_tpu import cli
    from idc_models_tpu import mesh as meshlib
    from idc_models_tpu.models.vgg import vgg16
    from idc_models_tpu.train import replicate, shard_batch

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != 4:
        sys.exit(f"needs the four-chip TPU host; jax found "
                 f"{len(devices)} {devices[0].platform} device(s)")
    rec: dict = {
        "device_kind": devices[0].device_kind,
        "jax": jax.__version__,
        # the order make_mesh reshapes: position in jax.devices() -> chip
        "device_order": [{"id": d.id, "coords": list(d.coords),
                          "core_on_chip": d.core_on_chip}
                         for d in devices],
        "verbs": {},
    }

    with tempfile.TemporaryDirectory() as tmp:
        for name, args in (
                ("vgg", ["vgg", "--epochs", "1", "--fine-tune-epochs", "1"]),
                ("fed", ["fed", "--rounds", "2", "--pretrain-epochs", "1"]),
                ("secure-fed", ["secure-fed", "--rounds", "2",
                                "--mask-impl", "pallas"])):
            before = _peaks(devices)
            t0 = time.perf_counter()
            rc = cli.main([*args, "--path", str(Path(tmp) / name)])
            events = [json.loads(line) for line in
                      (Path(tmp) / name / "logs" / "run.jsonl")
                      .read_text().splitlines()]
            rec["verbs"][name] = {
                "rc": rc, "wall_s": round(time.perf_counter() - t0, 1),
                "peak_bytes_in_use_before": before,
                "peak_bytes_in_use_after": _peaks(devices),
                "last_record": next(
                    e for e in reversed(events)
                    if e.get("event") in ("test", "round"))}
            print(f"=== {name}: rc={rc} peaks "
                  f"{rec['verbs'][name]['peak_bytes_in_use_after']}",
                  flush=True)

    # placement, by the trainer's own placing functions
    mesh = meshlib.data_mesh()
    imgs = np.zeros((32, 50, 50, 3), np.float32)
    labels = np.zeros((32,), np.int32)
    x, y = shard_batch(mesh, imgs, labels)
    kernel = max(jax.tree.leaves(
        replicate(mesh, vgg16(1).init(jax.random.key(0)).params)),
        key=lambda leaf: leaf.size)
    clients = jax.device_put(
        np.zeros((8, 64, 10, 10, 3), np.float32),
        meshlib.sharding(meshlib.client_mesh(4), meshlib.CLIENT_AXIS))
    rec["placement"] = {
        "batch_of_32_images": _shards(x), "batch_of_32_labels": _shards(y),
        f"replicated_param_{list(kernel.shape)}": _shards(kernel),
        "eight_stacked_clients": _shards(clients)}
    print("=== placement", json.dumps(rec["placement"]), flush=True)

    # four one-chip replicas: where each replica's KV caches landed
    # (built the way `serve-cluster` builds them: replica i on device i)
    from idc_models_tpu.models.lm import attention_lm
    from idc_models_tpu.serve import build_replica

    lm = dict(embed_dim=512, num_heads=8, num_blocks=2, t_max=2048)
    params = attention_lm(1024, lm["t_max"], embed_dim=512, num_heads=8,
                          mlp_dim=2048, num_blocks=2).init(
                              jax.random.key(0)).params
    rec["replica_cache_devices"] = {}
    for i, dev in enumerate(devices):
        rep = build_replica(params, replica_id=f"r{i}", device=dev,
                            n_slots=8, window=64, **lm)
        caches = jax.tree.leaves(rep.server.engine._caches)
        rec["replica_cache_devices"][f"r{i}"] = sorted(
            {d.id for leaf in caches for d in leaf.devices()})
        rep.server.close()
    print("=== replica caches", json.dumps(rec["replica_cache_devices"]),
          flush=True)

    # n devices against one device, on the real chips. The gates stop
    # at the first that fails, and the ring-attention gate compares two
    # ALGORITHMS (ring vs full attention) at 1e-5: at the TPU's default
    # matmul precision (bf16 passes) they differ by ~4e-3, so the gates
    # run a second time with full-f32 matmuls.
    rec["agreement_checks"] = {}
    for precision, ctx in (
            ("default", contextlib.nullcontext()),
            ("highest", jax.default_matmul_precision("highest"))):
        try:
            with ctx:
                graft.agreement_checks(4)
            outcome = "all gates passed"
        except AssertionError:
            outcome = traceback.format_exc()[-1500:]
        rec["agreement_checks"][precision] = outcome
        print(f"=== agreement at {precision} matmul precision:", outcome,
              flush=True)

    line = json.dumps(rec) + "\n"
    for out in (ROOT / "experiments" / "four_chip_bringup.jsonl",
                ROOT / "chiprun_out" / "four_chip_bringup.jsonl"):
        out.parent.mkdir(exist_ok=True)
        with open(out, "a") as f:
            f.write(line)


if __name__ == "__main__":
    main()
