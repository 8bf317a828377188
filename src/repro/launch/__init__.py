"""Launchers: production mesh, allocation-free dry-run, train driver."""
