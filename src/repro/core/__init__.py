"""Fused compute-collective operators; the public API is
:mod:`repro.core.fused`."""
