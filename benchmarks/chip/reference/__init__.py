"""The plain reference of the assessment: parser, encoder, metrics and
HyperLogLog registers, and the comparison that decides ``correct``."""
