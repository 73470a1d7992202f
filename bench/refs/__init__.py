"""Part of the chip benchmark; see ``bench/README.md``."""
