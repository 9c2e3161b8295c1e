"""The harness: general code that every cell, mix and metric shares."""
