"""CPU tests of the chip benchmark."""
