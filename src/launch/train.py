"""Alias module — see :mod:`repro.launch.train`."""
from repro.launch.train import cli, main  # noqa: F401

if __name__ == "__main__":
    cli()
