"""Deployment configurations of the twin: one module per datacenter, each
building its ``DatacenterConfig`` and power rows from its published
source."""
