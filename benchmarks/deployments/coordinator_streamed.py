"""`coordinator.py`'s deployment for a configuration that keeps no table
on the chip (`"serve": "coordinator_streamed"`): the same catalog,
`Session` and `CoordinatorServer`, held to the two things such a cell
rests on.

Before anything is generated, the program's streamed executor has to
open spans (`obs.span.Pulled`: `exec/stream.py` opens one a plan node
and books the driver loop's reads under it). A program from before them
reports this cell's `host_reads_per_stmt` and `dispatch_ms` wrong, has
none of the `stream_*` metrics, and needs 447-670 s a run of
`sf10s.scan_agg` where the driver allows 360 (PERF.md section 6, PR 35):
it cannot run the configuration, and exits here, non-zero, in seconds.

When the server stops, the configuration's `residency` guarantee, as far
as a run can show it: the bytes in use on the device are under the
session's `memory_budget`. A table page kept there between statements
is past it (Q6's four columns of SF10 lineitem are 1.68 GB; 12.6 MB are
in use after a streamed statement), and the run ends with no result.
"""

from deployments.coordinator import Deployment


class StreamedDeployment(Deployment):
    def __init__(self, config):
        from presto_tpu.obs import span

        if not hasattr(span, "Pulled"):
            raise SystemExit(
                f"{config['serve']}: this program's streamed executor opens "
                "no spans (presto_tpu.obs.span has no `Pulled`); it cannot "
                "run a streamed cell"
            )
        super().__init__(config)
        self.budget = int(config["session"]["memory_budget"])

    def stop(self):
        super().stop()
        import jax

        stats = jax.devices()[0].memory_stats() or {}  # None on the CPU
        held = int(stats.get("bytes_in_use", 0))
        if held > self.budget:
            raise SystemExit(
                f"residency: {held} B in use on the device once the server "
                f"has stopped, past the session's memory_budget {self.budget}"
            )


def start(config) -> StreamedDeployment:
    return StreamedDeployment(config)
