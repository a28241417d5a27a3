"""Drivers, one per kind of traffic, found by the traffic file's ``kind``."""
