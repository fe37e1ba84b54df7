"""One coordinator and engine in one process on one chip, as `python -m
presto_tpu.cli --serve` starts them: the configuration's catalog class
at its scale factor, a `Session` with the configuration's settings, and
`CoordinatorServer` on a free port. `"serve": "coordinator"` in a
configuration names this module; a mesh or a cluster of workers is
another module here, found by name."""

import importlib


class Deployment:
    def __init__(self, config):
        from presto_tpu.server import CoordinatorServer
        from presto_tpu.session import Session

        cat = config["catalog"]
        catalog_cls = getattr(
            importlib.import_module(cat["module"]), cat["class"]
        )
        session = Session(
            catalog_cls(sf=config["sf"], **cat.get("args", {})),
            **config["session"],
        )
        self.server = CoordinatorServer(session, port=0).start()

    def client(self):
        from presto_tpu.server import Client

        return Client(self.server.uri, timeout=600.0)

    def stop(self):
        self.server.stop()


def start(config) -> Deployment:
    return Deployment(config)
