"""Configuration files of the chip benchmark, with their plain references."""
