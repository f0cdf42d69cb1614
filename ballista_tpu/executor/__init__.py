"""Executor: task runner, shuffle data plane, Flight service, daemons.

The reference's executor crate (ballista/rust/executor/src): poll loop /
push server for task execution, ShuffleWriter materialization to Arrow IPC
files, and an Arrow Flight `do_get` service for shuffle fetches.
"""


def visible_devices() -> int:
    """Device count this process advertises
    (ExecutorSpecification.n_devices)."""
    import jax

    return len(jax.devices())


def effective_task_slots(task_slots: int) -> int:
    """A device MESH is one resource: concurrent task threads would
    contend for the XLA worker pool and can starve a collective program's
    per-device partitions into a rendezvous deadlock (observed on a
    virtual CPU mesh). Mesh stage-chains fuse whole pipelines into one
    task anyway — run them serially. Shared by the pull loop and the push
    server so both modes keep identical concurrency policy."""
    if visible_devices() >= 2 and task_slots > 1:
        return 1
    return task_slots
