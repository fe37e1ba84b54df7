"""Shard 1 of the TPC-DS oracle suite (cases.py says why it is sharded)."""

import pytest

from cases import check, shard


@pytest.mark.parametrize("qid", shard(1))
def test_tpcds_query(session, oracle, qid):
    check(session, oracle, qid)
