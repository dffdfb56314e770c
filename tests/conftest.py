"""Pytest config — NOTE: no XLA_FLAGS here; smoke tests run single-device.
Multi-device coverage lives in test_distributed.py via subprocesses."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests (dry-run cells)")
