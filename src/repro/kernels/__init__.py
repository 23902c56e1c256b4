"""Pallas kernels of the twin: the calibration grid (``calib_mape``) and
the fused DES read-out (``des_readout``), each with its pure-jnp oracle
and dispatched through ``ops`` by backend."""
