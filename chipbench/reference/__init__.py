"""The plain reference: the accelerator's physics and the evaluation
networks, independent of the compiler under test."""
