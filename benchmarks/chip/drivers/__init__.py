"""Drivers: the general generators a traffic file names by ``driver``."""
