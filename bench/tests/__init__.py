"""CPU tests of the chip benchmark's yardstick and harness."""
