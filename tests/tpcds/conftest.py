"""What every shard of the TPC-DS suite shares (see cases.py): one catalog
and one loaded oracle per shard file."""

import pytest

from cases import SF
from presto_tpu.connectors import tpcds
from presto_tpu.connectors.tpcds import TpcdsCatalog
from presto_tpu.session import Session
from presto_tpu.testing.oracle import SqliteOracle


@pytest.fixture(scope="module")
def session():
    return Session(TpcdsCatalog(sf=SF))


@pytest.fixture(autouse=True)
def _clear_jax_caches():
    """73 distinct query pipelines compile thousands of XLA executables;
    one process accumulates them until native allocation fails (observed
    as a segfault around the 60th query). Each query is unique, so the
    cache buys nothing across tests — drop it."""
    yield
    import jax

    jax.clear_caches()


@pytest.fixture(scope="module")
def oracle():
    return SqliteOracle(sf=SF, source=tpcds)
