"""Multi-tenant serving plane (docs/multitenancy.md): tenant identity
and quotas at the frontend, deficit-weighted fair-share admission in
the engines, per-tenant KV budgets, and always-on `dynamo_tenant_*`
fairness surfaces. Armed by DYN_TENANCY; unarmed fleets run the legacy
paths byte-identical."""

from dynamo_tpu.tenancy.config import (  # noqa: F401
    ANON_TENANT,
    TENANT_HEADER,
    TenancyConfig,
    Tenant,
    parse_tenancy,
    tenancy_from_env,
)
from dynamo_tpu.tenancy.fair import FairScheduler, tenant_state  # noqa: F401
from dynamo_tpu.tenancy.metrics import TenantMetrics  # noqa: F401
from dynamo_tpu.tenancy.quota import (  # noqa: F401
    QuotaGate,
    TokenBucket,
    estimate_request_tokens,
    retry_after_header,
)
