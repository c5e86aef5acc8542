"""repro — a scalable JAX/Pallas framework for RDF quality assessment.

Public entry point: ``repro.qa`` (fluent pipeline + one-call assess).
Engine layers: ``repro.core`` (QAP metrics/planner/evaluator),
``repro.dist`` (chunk scheduling, sharding, fault tolerance),
``repro.rdf`` (parse/encode/TripleTensor), ``repro.kernels`` (Pallas).
"""
