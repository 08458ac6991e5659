import os

# Tests must see the real single CPU device — never forced host-platform
# placeholders (a four-device rehearsal sets XLA_FLAGS in its own process).
assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""), (
    "run pytest without --xla_force_host_platform_device_count in XLA_FLAGS"
)

import pytest


@pytest.fixture()
def store(tmp_path):
    from repro.core import TwoLevelStore

    with TwoLevelStore(
        str(tmp_path / "pfs"),
        mem_capacity_bytes=8 * 2**20,
        block_bytes=1 * 2**20,
        n_pfs_servers=2,
        stripe_bytes=256 * 1024,
    ) as st:
        yield st
