"""Renewal engines' share of their roofline over the traced window."""
from bench.metrics._device import renewal_roofline as read  # noqa: F401
