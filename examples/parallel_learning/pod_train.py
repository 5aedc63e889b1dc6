"""Pod-scale distributed training walkthrough (one process per host).

The SPMD translation of the reference's parallel-learning guide
(ref: docs/Parallel-Learning-Guide.rst:58+ — build a machine list, pick
ports, start N copies): here every host runs THIS script unchanged; the
launcher contract (LGBM_TPU_* env vars, or TPU-pod auto-detection with
no env at all) wires the world, and the global mesh spans every host's
chips. Collectives ride ICI/DCN via XLA — no machine list, no ports.

Launch examples:

  # TPU pod (GKE/QR): just run it on every host — zero config
  python pod_train.py

  # any generic launcher (SLURM, mpirun, k8s): set the env contract
  LGBM_TPU_COORDINATOR=host0:8476 LGBM_TPU_NUM_PROCESSES=4 \
  LGBM_TPU_PROCESS_ID=$RANK python pod_train.py

  # localhost rehearsal without hardware (2 procs x 2 virtual devices)
  python -c "from lightgbm_tpu.distributed import launch_local; \
             print(launch_local(['python', 'pod_train.py'], 2, \
                                cpu_devices_per_process=2))"

  # SUPERVISED rehearsal (ISSUE 10 fault-tolerant gang): per-rank
  # heartbeat supervision, rank death SIGTERMs the survivors, and the
  # whole gang auto-relaunches from the newest gang manifest — one
  # rank death costs one resume, not the session
  python pod_train.py --local-gang 2

Each process loads ITS OWN row shard (per-rank slice here; a per-rank
file via 'data_{rank}.csv' works the same) and ``pre_partition=true``
engages sharded ingestion: distributed bin finding (per-shard sample
summaries → feature-sliced find_bin → BinMapper allgather) makes the
bin boundaries globally identical, each host bins only its rows, and
the device mesh is fed from the process-local shards — host RAM per
process is O(rows/world), the reference's 176 GB/machine Criteo recipe
(src/io/dataset_loader.cpp:1175-1219) in SPMD form. See
docs/TPU_RUNBOOK.md "Sharded ingestion".
"""
import os
import sys

# runnable straight from a repo checkout (drop when pip-installed)
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))

_GANG_FLAG = "--local-gang"
_LAUNCHER = _GANG_FLAG in sys.argv

if not _LAUNCHER:
    from lightgbm_tpu.distributed import init_from_env  # noqa: E402

    rank = init_from_env()      # must precede any other jax use

    import numpy as np          # noqa: E402

    import lightgbm_tpu as lgb  # noqa: E402
    from lightgbm_tpu.distributed import (num_processes,  # noqa: E402
                                          row_slice)

N_ROWS = int(os.environ.get("POD_TRAIN_ROWS", 40_000))
N_FEATURES = 16
_GEN_BLOCK = 8192


def load_data(rank: int, world: int):
    """THIS process's row shard only — no host ever holds the global
    table. Synthetic data keeps the walkthrough runnable anywhere: the
    deterministic global table is defined in fixed 8192-row blocks,
    each seeded by its block index, and a rank materializes ONLY the
    blocks overlapping its slice — every world size trains on the same
    logical rows at O(rows/world) host memory (a real deployment reads
    a per-rank file or slice instead, e.g.
    ``lgb.Dataset("higgs_{rank}.csv", params={"pre_partition": True})``).
    """
    lo, hi = row_slice(N_ROWS, rank, world)
    parts = []
    for b in range(lo // _GEN_BLOCK, (max(hi, lo + 1) - 1) // _GEN_BLOCK + 1):
        b_lo = b * _GEN_BLOCK
        n_blk = min(b_lo + _GEN_BLOCK, N_ROWS) - b_lo
        blk = np.random.default_rng([7, b]).normal(
            size=(n_blk, N_FEATURES)).astype(np.float32)
        parts.append(blk[max(lo - b_lo, 0):hi - b_lo])
    X = np.concatenate(parts, axis=0)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2] * X[:, 3] > 0)
    return X, y.astype(np.float32)


def main() -> None:
    world = num_processes()
    X, y = load_data(rank, world)
    # fault tolerance (ISSUE 10): with a checkpoint dir set, rank 0
    # commits CRC checkpoints + gang manifests (world size, per-rank
    # shard digests) and EVERY rank resumes from the newest committed
    # manifest — the supervised launcher below relaunches a failed
    # gang through exactly this path
    ckpt_dir = os.environ.get("POD_TRAIN_CKPT_DIR", "")
    callbacks = []
    if ckpt_dir and rank == 0:
        callbacks.append(lgb.checkpoint_callback(
            ckpt_dir, every_n=int(os.environ.get("POD_TRAIN_CKPT_EVERY",
                                                 "5")), keep_last=5))
    bst = lgb.train(
        {"objective": "binary", "tree_learner": "data",
         "num_leaves": 63, "learning_rate": 0.1, "verbose": -1,
         # sharded ingestion: per-host row shards, distributed bin
         # finding, O(rows/world) host memory
         "pre_partition": True,
         # bit-identical across world sizes: exact int32 histogram
         # accumulation under the global scales
         "use_quantized_grad": True, "stochastic_rounding": False,
         "deterministic": True, "seed": 7},
        lgb.Dataset(X, label=y), num_boost_round=30,
        callbacks=callbacks, resume_from=ckpt_dir or None)
    if rank == 0:
        bst.save_model("pod_model.txt")
        pred = bst.predict(X)
        acc = float(np.mean((pred > 0.5) == y))
        print(f"[pod_train] world={world} shard_rows={len(X)} "
              f"train-shard acc={acc:.4f} model -> pod_model.txt",
              flush=True)


def _launch_gang() -> None:
    """``--local-gang N``: run N ranks of THIS script as a SUPERVISED
    fault-tolerant gang (robustness/gang.py). The launcher never runs a
    jax op or initializes a backend — supervisor discipline: backend
    init is what hangs on a wedged device — and a mid-run rank death
    SIGTERMs the survivors and relaunches the gang, resuming from the
    newest valid gang manifest in POD_TRAIN_CKPT_DIR (a tmpdir by
    default)."""
    import tempfile

    from lightgbm_tpu.robustness.gang import run_supervised

    i = sys.argv.index(_GANG_FLAG)
    world = (int(sys.argv[i + 1])
             if len(sys.argv) > i + 1 and sys.argv[i + 1].isdigit()
             else 2)
    ckpt = os.environ.get("POD_TRAIN_CKPT_DIR") or \
        tempfile.mkdtemp(prefix="pod_train_ckpt_")
    results = run_supervised(
        [sys.executable, os.path.abspath(__file__)], world,
        cpu_devices_per_process=int(
            os.environ.get("POD_TRAIN_DEVICES", "2")),
        timeout=float(os.environ.get("POD_TRAIN_TIMEOUT", "600")),
        env_extra={"POD_TRAIN_CKPT_DIR": ckpt},
        label="pod_train gang")
    for r, (rc, out) in enumerate(results):
        print(f"--- rank {r} (rc={rc}) ---\n{out}", end="", flush=True)


if __name__ == "__main__":
    if _LAUNCHER:
        _launch_gang()
    else:
        main()
