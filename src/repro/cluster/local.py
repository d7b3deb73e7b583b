"""Localhost worker fleet: the real wire protocol on one machine.

``LocalCluster(hosts)`` spawns one worker PROCESS per host on loopback
ephemeral ports and reports their addresses, so tests, the smoke canary,
and ``bench_serving --hosts`` exercise the exact coordinator/worker
protocol — framing, bound broadcast, heartbeats, death handling — with
no second machine.

The ``spawn`` start method is deliberate and load-bearing: each worker
must be a FRESH interpreter because the parent has usually initialized
jax (a fork-child of a jax-initialized process must never dispatch jax
ops), and because a real deployment's workers are independent processes
too — fork would quietly share page-cache state the wire protocol is
supposed to carry. Workers announce their bound ``(host, port)`` back
over a pipe before serving.

Every worker runs JAX on the CPU, set in the child's own environment
(``JAX_PLATFORMS=cpu``) rather than left to JAX's fallback: on a host
whose one chip belongs to the parent, a worker that asked for the
accelerator would fail or hang. Workers report the platform they run on
in their ``ready`` frame (``EngineStats.per_host``).

``kill_worker(i)`` SIGKILLs one worker — the failure-injection hook the
killed-worker tests use; ``close()`` terminates the fleet (idempotent).
"""

from __future__ import annotations

import multiprocessing as mp
import os
from typing import List, Tuple

__all__ = ["LocalCluster"]


def _worker_main(announce) -> None:
    # runs in the spawned interpreter, before its JAX picks a backend
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from repro.cluster.worker import serve

    serve(host="127.0.0.1", port=0, announce=announce)


class LocalCluster:
    """``hosts`` spawned loopback workers; ``addresses[i]`` is worker
    ``i``'s ``(host, port)``. Every worker runs JAX on the CPU."""

    def __init__(self, hosts: int, start_timeout: float = 120.0):
        if hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {hosts}")
        ctx = mp.get_context("spawn")
        self.procs: List[mp.Process] = []
        self.addresses: List[Tuple[str, int]] = []
        pipes = []
        try:
            for _ in range(hosts):
                parent, child = ctx.Pipe()
                proc = ctx.Process(
                    target=_worker_main, args=(child,), daemon=True
                )
                proc.start()
                child.close()
                self.procs.append(proc)
                pipes.append(parent)
            for i, parent in enumerate(pipes):
                if not parent.poll(start_timeout):
                    raise RuntimeError(
                        f"worker {i} did not announce its address "
                        f"within {start_timeout:.0f}s"
                    )
                self.addresses.append(tuple(parent.recv()))
                parent.close()
        except BaseException:
            self.close()
            raise

    def kill_worker(self, i: int) -> None:
        """SIGKILL worker ``i`` — no shutdown handshake, the coordinator
        sees a raw connection drop. Failure-injection hook for tests."""
        self.procs[i].kill()
        self.procs[i].join(timeout=10.0)

    def close(self) -> None:
        for proc in self.procs:
            if proc.is_alive():
                proc.terminate()
        for proc in self.procs:
            proc.join(timeout=10.0)
            if proc.is_alive():
                proc.kill()
                proc.join(timeout=5.0)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass   # interpreter shutdown
